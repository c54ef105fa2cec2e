"""The arithmetic behind the metric readers in ``bench/metrics/``.

Each reader file binds one of these to its name; a quantity split by the
end-to-end metric it moves (``decode_mfu.serve``) binds the same function
under each of its names. A reader returns ``None``
when its run has nothing to read: a trace metric without a trace, or a
program or kernel that did not run in the traced window. A share of a
peak or a roofline is never made up as 0.

Device programs and kernels are found by the names the trace prints for
them, as the program has no ``named_scope`` of its own yet:

* prefill: the program ``jit__lambda`` (``PrefillEngine``'s jitted step);
* decode chunk: the program ``jit_decode_chunk``;
* ``kv_quant``: the kernel ``kv_quant`` inside the program ``jit_kv_quant``;
* paged attention: the kernel ``paged_decode_attention`` inside
  ``jit_decode_chunk``.
"""
from __future__ import annotations

from typing import Optional

from bench import flops, stats

PREFILL_MODULE = "jit__lambda"
DECODE_MODULE = "jit_decode_chunk"
KV_QUANT_MODULE = "jit_kv_quant"
KV_QUANT_KERNEL = "kv_quant"
PAGED_KERNEL = "paged_decode_attention"


def _pct(x: float) -> float:
    return 100.0 * x


# -- end to end (host clock, trace off) ------------------------------------

def ttft_p95_s(run) -> Optional[float]:
    return stats.percentile(stats.ttfts(run.log), 95)


def tpot_p95_s(run) -> Optional[float]:
    return stats.percentile(stats.tpots(run.log), 95)


def setup_s(run) -> Optional[float]:
    return run.setup_s


# -- per layer --------------------------------------------------------------

def queue_wait_p95_s(run) -> Optional[float]:
    """Gateway: due time to leaving QUEUED, 95th percentile over the
    window's requests (from ``RequestHandle.history``)."""
    return stats.percentile(
        [stats.queue_wait(s, run.log.t_cap) for s in run.log.in_window()], 95)


def prefill_mfu(run) -> Optional[float]:
    """Prefill engine: FLOPs of the unpadded prompt tokens of the prefill
    calls wholly inside the traced window, over the prefill program's
    device time inside those calls' host spans, times the peak."""
    if run.trace is None:
        return None
    t = run.trace.span_module_s.get("bench.prefill", {}).get(PREFILL_MODULE,
                                                             0.0)
    work = sum(flops.prefill_flops(run.dims, n)
               for p in run.traced_prefills() for n in p.true_lens)
    if t <= 0 or work <= 0:
        return None
    return _pct(work / (t * run.peaks["bf16_flops_per_s"]))


def kv_quant_roofline(run) -> Optional[float]:
    """KV wire: the bytes the int4 quantizer must move for the prefill
    buckets of the traced window, at peak bandwidth, over its kernel time."""
    if run.trace is None:
        return None
    t = run.trace.kernel_time(KV_QUANT_MODULE, KV_QUANT_KERNEL)
    nbytes = sum(flops.kv_quant_bytes(flops.wire_rows(run.dims, b,
                                                      run.group), run.group)
                 for p in run.traced_prefills() for b in p.buckets)
    if t <= 0 or nbytes <= 0:
        return None
    return _pct(flops.roofline_s(0.0, nbytes, run.peaks) / t)


def _decode_work(run):
    tok_flops, attn_flops, attn_bytes = 0.0, 0.0, 0.0
    for c in run.traced_chunks():
        for ctx, e in zip(c.ctx, c.emitted):
            for keys in flops.chunk_keys(ctx, e):
                tok_flops += flops.decode_token_flops(run.dims, keys)
        steps = max(c.emitted, default=0)
        for j in range(steps):
            keys = [ctx + j + 1 for ctx, e in zip(c.ctx, c.emitted) if j < e]
            attn_flops += run.dims["L"] * flops.paged_attn_flops(run.dims,
                                                                 keys)
            attn_bytes += run.dims["L"] * flops.paged_attn_bytes(
                run.dims, keys, run.group)
    return tok_flops, attn_flops, attn_bytes


def decode_mfu(run) -> Optional[float]:
    """Decode engine: FLOPs of the live slots' tokens in the chunks wholly
    inside the traced window, over the chunk program's device time inside
    those chunks' host spans, times the peak."""
    if run.trace is None:
        return None
    t = run.trace.span_module_s.get("bench.decode_step", {}).get(
        DECODE_MODULE, 0.0)
    work, _, _ = _decode_work(run)
    if t <= 0 or work <= 0:
        return None
    return _pct(work / (t * run.peaks["bf16_flops_per_s"]))


def paged_attn_roofline(run) -> Optional[float]:
    """Kernel: least time for the live slots' attention (packed nibbles,
    scales, queries, outputs; FLOPs), over the kernel's device time."""
    if run.trace is None:
        return None
    t = run.trace.kernel_time(DECODE_MODULE, PAGED_KERNEL)
    _, f, b = _decode_work(run)
    if t <= 0 or b <= 0:
        return None
    return _pct(flops.roofline_s(f, b, run.peaks) / t)


def device_idle_share(run) -> Optional[float]:
    """Device: window time in which no operation ran."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return _pct(1.0 - run.trace.busy_s / run.trace.window_s)

