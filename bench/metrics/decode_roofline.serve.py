"""Metric ``decode_roofline.serve`` (decode engine): the least time the
chip could take for the steps of the decode chunks wholly inside the
traced window, over the chunk program's device time inside those chunks'
host spans (the time ``decode_mfu.serve`` divides by).

A step's least time is ``flops.roofline_s`` of its FLOPs (each live
slot's token at its own context, ``flops.decode_token_flops``) and of the
bytes it must move at least once (:func:`step_bytes`): every layer's
matrices and the head's ``d x V`` in bf16, read once whatever the batch,
and the live slots' paged int4 KV with their queries and outputs
(``flops.paged_attn_bytes``). A step counts where some slot emitted a
token in it. None without a trace or a chunk in it."""
from typing import Dict, Optional, Sequence

from bench import flops
from bench.layer_metrics import DECODE_MODULE

WEIGHT_BYTES = 2        # bf16 matrices


def step_bytes(k: Dict[str, int], keys: Sequence[int], group: int) -> float:
    """Bytes one decode step moves at least, for live slots attending over
    ``keys`` positions each."""
    weights = WEIGHT_BYTES * (k["L"] * flops.layer_matmul_params(k)
                              + k["d"] * k["V"])
    return weights + k["L"] * flops.paged_attn_bytes(k, keys, group)


def read(run) -> Optional[float]:
    if run.trace is None:
        return None
    t = run.trace.span_module_s.get("bench.decode_step", {}).get(
        DECODE_MODULE, 0.0)
    least = 0.0
    for c in run.traced_chunks():
        for j in range(max(c.emitted, default=0)):
            keys = [ctx + j + 1 for ctx, e in zip(c.ctx, c.emitted) if j < e]
            work = sum(flops.decode_token_flops(run.dims, n) for n in keys)
            least += flops.roofline_s(work,
                                      step_bytes(run.dims, keys, run.group),
                                      run.peaks)
    if t <= 0 or least <= 0:
        return None
    return 100.0 * least / t
