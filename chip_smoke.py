"""Bring-up check of the serving main path on one TPU chip.

Builds gemma-2b at its published widths (18 layers, d_model 2048, vocab
256000) with random weights from ``--seed`` and serves a few open-loop
requests through the normal entry points: one ``PrefillEngine``, two paged
int4 ``DecodeEngine``s with prefix sharing, and a ``Gateway`` that moves
the KV cache over the int4 wire. Before that it checks the Pallas kernels
of that path against their jnp references at the same widths, and a
decode step's logits through the paged Pallas kernel against the
reference step on the same cache.

  python chip_smoke.py [--seed 0]

Needs a TPU: on any other platform it exits non-zero and names the platform
it found. Every phase runs in this one process. Each phase prints its own
lines; the last line of a passing run is one JSON object naming the
device. Any failed phase makes the exit code 1.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "gemma-2b"
PUBLISHED = dict(num_layers=18, d_model=2048, vocab_size=256000)
PAGE_SIZE = 16
MAX_SEQ = 2048
MAX_SLOTS = 4
# prompt lengths of the trace: three prefill buckets (128, 512, 1024)
BUCKETS = (128, 512, 1024)
PROMPT_LENS = (128, 300, 1000, 512, 700, 128, 450, 1024)
MAX_NEW = 32            # tokens generated per request
RATE = 4.0              # Poisson arrivals per second
# compiles between pumps are not replica deaths
HEARTBEAT_TIMEOUT_S = 300.0

# parity tolerances, all against the jnp reference on the same inputs
TOL_DEQUANT = 1e-5      # max abs error / max |ref|, f32 output
TOL_ATTN = 1e-2         # relative L2 error, int4 paged attention, f32 q
TOL_LOGITS = 5e-2       # relative L2 error per slot, decode-step logits
# JAX's duration event around each XLA compile or compile-cache lookup
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def rel_l2(a, b):
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def kernel_parity(cfg, seed):
    """kv_quant / kv_dequant / int4 paged attention: Pallas vs reference
    at the model's widths (group g, ``page_size * ppr`` rows per page)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.models import paged

    g = paged.page_group(cfg)
    ppr = paged.groups_per_token(cfg)
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    gq = cfg.num_heads // Hkv
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    # one bucket-1024 wire: K and V of every layer, position-aligned rows
    n = 2 * cfg.num_layers * BUCKETS[-1] * ppr
    x = jax.random.normal(k1, (n, g), jnp.float32).astype(jnp.bfloat16)
    qp, sp, zp = ops.kv_quant(x, backend="pallas")
    qr, sr, zr = ops.kv_quant(x, backend="ref")
    bad = int(jnp.sum(qp != qr))
    ds = float(jnp.max(jnp.abs(sp - sr)))
    dz = float(jnp.max(jnp.abs(zp - zr)))
    print(f"  kv_quant: {bad} of {qr.size} packed bytes differ from "
          f"kv_quant_ref; max |scale diff| {ds:.3g}, |zero diff| {dz:.3g}")
    ok = True
    if bad or ds or dz:
        # a rounding tie may flip a nibble: then the dequantized values
        # must still lie within one quantization step of the reference
        dp = ops.kv_dequant(qp, sp, zp, out_dtype=jnp.float32, backend="ref")
        dr = ops.kv_dequant(qr, sr, zr, out_dtype=jnp.float32, backend="ref")
        worst = float(jnp.max(jnp.abs(dp - dr) / sr))
        print(f"    dequantized values differ by at most {worst:.3f} "
              f"quantization steps (limit 1)")
        ok &= worst <= 1.0 + 1e-3
    dp = ops.kv_dequant(qr, sr, zr, out_dtype=jnp.float32, backend="pallas")
    dr = ops.kv_dequant(qr, sr, zr, out_dtype=jnp.float32, backend="ref")
    err = float(jnp.max(jnp.abs(dp - dr)))
    rel = err / float(jnp.max(jnp.abs(dr)))
    print(f"  kv_dequant: max abs err {err:.3g}, max rel err {rel:.3g} "
          f"(limit {TOL_DEQUANT})")
    ok &= rel <= TOL_DEQUANT

    # int4 paged attention, ragged lengths, pages scattered over the pool,
    # read at the second layer of a two-layer stacked pool
    W = paged.table_width(MAX_SEQ, PAGE_SIZE)
    P = MAX_SLOTS * W + 1
    R = PAGE_SIZE * ppr
    rows = jax.random.normal(k2, (2 * 2 * P * R, g), jnp.float32)
    kq, ks, kz = ops.kv_quant(rows, backend="ref")
    kq = kq.reshape(2, 2, P, R, -1)                  # (K/V, layer, ...)
    ks, kz = (a.reshape(2, 2, P, R) for a in (ks, kz))
    pages = {"kp": kq[0], "vp": kq[1],
             "sz": jnp.stack([ks[0], kz[0], ks[1], kz[1]], axis=2)}
    perm = np.asarray(jax.random.permutation(k3, np.arange(1, P)))
    table = jnp.asarray(perm.reshape(MAX_SLOTS, W).astype(np.int32))
    lens = jnp.asarray([MAX_SEQ, MAX_SEQ // 2 - 24, 17, 1], jnp.int32)
    q = jax.random.normal(k4, (MAX_SLOTS, Hkv, gq, hd), jnp.float32)
    outs = [ops.paged_decode_attention(q, pages, table, lens, 1,
                                       page_size=PAGE_SIZE, backend=b)
            for b in ("pallas", "ref")]
    err = float(jnp.max(jnp.abs(outs[0] - outs[1])))
    r = rel_l2(outs[0], outs[1])
    print(f"  paged attention (int4, {P} pages, lengths "
          f"{[int(v) for v in lens]}): max abs err {err:.3g}, "
          f"rel L2 err {r:.3g} (limit {TOL_ATTN})")
    ok &= bool(np.isfinite(np.asarray(outs[0])).all()) and r <= TOL_ATTN
    return ok


def logits_parity(cfg, api, params, prefill, rng):
    """One paged decode step through the Pallas and the reference step
    functions on the same int4 cache, filled by real prefill wires."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import paged
    from repro.serving import page_pool
    from repro.serving.engine import GenRequest

    W = paged.table_width(MAX_SEQ, PAGE_SIZE)
    cache = paged.init_paged_cache(cfg, MAX_SLOTS, MAX_SEQ, MAX_SLOTS * W + 1,
                                   page_size=PAGE_SIZE)
    lens = PROMPT_LENS[:MAX_SLOTS]
    reqs = [GenRequest(i, rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                       1) for i, n in enumerate(lens)]
    items, firsts = [], {}
    for r, wire, first in prefill.run(reqs):
        pages = list(range(1 + r.rid * W, 1 + r.rid * W
                           + page_pool.pages_needed(len(r.tokens) + 1,
                                                    PAGE_SIZE)))
        items.append((wire, r.rid, pages))
        firsts[r.rid] = first
    cache, n_zero, _ = page_pool.insert_wires(cache, cfg, items)
    tokens = jnp.asarray([[firsts[i]] for i in range(MAX_SLOTS)], jnp.int32)
    out = {}
    for b in ("pallas", "ref"):
        step, _ = api.paged_decode_fns(PAGE_SIZE, b)
        logits, _ = jax.jit(step)(params, cache, {"tokens": tokens})
        out[b] = np.asarray(logits[:, -1], np.float32)
    pal, ref = out["pallas"], out["ref"]
    errs = [rel_l2(pal[i], ref[i]) for i in range(MAX_SLOTS)]
    mabs = float(np.max(np.abs(pal - ref)))
    agree = int(np.sum(pal.argmax(-1) == ref.argmax(-1)))
    print(f"  decode-step logits, prompts {list(lens)} ({n_zero} zero-copy "
          f"page inserts): rel L2 err per slot "
          f"{[f'{e:.3g}' for e in errs]} (limit {TOL_LOGITS}), max abs err "
          f"{mabs:.3g} of max |logit| {float(np.max(np.abs(ref))):.3g}; "
          f"argmax agrees on {agree}/{MAX_SLOTS} (informational)")
    finite = all(np.isfinite(v).all() for v in out.values())
    return finite and max(errs) <= TOL_LOGITS


def build_gateway(cfg, params):
    """The serving path, from the same public constructors the examples
    use. One-prompt prefill batches keep the compile count at one program
    per length bucket."""
    from repro.serving.engine import DecodeEngine, PrefillEngine
    from repro.serving.gateway import Gateway, SchedulerConfig
    from repro.serving.transport import InProcessTransport

    prefill = PrefillEngine(cfg, params, max_seq=MAX_SEQ, max_batch=1)
    decodes = [DecodeEngine(cfg, params, max_slots=MAX_SLOTS, max_seq=MAX_SEQ,
                            paged=True, page_size=PAGE_SIZE,
                            prefix_sharing=True) for _ in range(2)]
    for d in decodes:
        if not d.paged:
            raise RuntimeError(f"decode engine is not paged: "
                               f"{d.paged_fallback}")
    gw = Gateway([prefill], decodes, transport=InProcessTransport(),
                 scheduler=SchedulerConfig(max_prefill_batch=1),
                 compress=True, backend="auto",
                 heartbeat_timeout=HEARTBEAT_TIMEOUT_S)
    return gw, prefill, decodes


def serve(cfg, gw, decodes, rng):
    """Warm every bucket, then serve an open-loop Poisson trace. The last
    request repeats the first prompt (a prefix-cache hit once the first
    has finished). Returns the summary of the handles."""
    import numpy as np

    from repro.serving.gateway import (DONE, ServeRequest, drive_open_loop,
                                       summarize_handles, warmup_gateway)

    t = time.perf_counter()
    warmup_gateway(gw, cfg.vocab_size, prompt_lens=BUCKETS)
    print(f"  warmup over buckets {list(BUCKETS)}: "
          f"{time.perf_counter() - t:.3f}s")
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS]
    prompts[-1] = prompts[0]
    arrivals, at = [], 0.0
    for rid, toks in enumerate(prompts):
        at += rng.exponential(1.0 / RATE)
        arrivals.append((at, ServeRequest(rid, toks, max_new_tokens=MAX_NEW)))
    t = time.perf_counter()
    handles = drive_open_loop(gw, arrivals)
    wall = time.perf_counter() - t
    s = summarize_handles(handles)
    zero = sum(d.page_stats()["zero_copy_inserts"] for d in decodes)
    reenc = sum(d.page_stats()["reencoded_inserts"] for d in decodes)
    pfx = gw.stats()["prefix"]
    print(f"  {s['n_done']}/{s['n_submitted']} requests DONE "
          f"(states {s['states']}), {s['tokens']} tokens in {wall:.3f}s")
    print(f"  TTFT p50 {s['ttft_p50_s']:.4f}s p99 {s['ttft_p99_s']:.4f}s, "
          f"TPOT p50 {s['tpot_p50_s']:.4f}s (host clock, informational)")
    print(f"  zero-copy page inserts {zero}, re-encoded {reenc}; prefix "
          f"cache {pfx['hits']} full / {pfx['partial_hits']} partial hits, "
          f"{pfx['misses']} misses")
    ok = s["states"].get(DONE, 0) == len(prompts) and zero > 0
    return ok, s


def decode_hlo_has_kernel(dec):
    """Compile the engine's own jitted paged decode chunk and look for the
    Pallas custom call."""
    return "tpu_custom_call" in dec._chunk.lower(
        dec.params, dec.cache, dec._host_state(), n_steps=dec.chunk_size,
        eos_id=dec.eos_id, max_seq=dec.max_seq).compile().as_text()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: {ROOT} holds no repro package (src/repro); run "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    import numpy as np

    compile_s = [0.0, 0]

    def on_duration(event, duration, **_):
        if event == COMPILE_EVENT:
            compile_s[0] += duration
            compile_s[1] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    print(f"device {dev.platform} / {dev.device_kind} x {len(jax.devices())};"
          f" compile cache {cache_dir}")

    from repro.configs import get_config
    from repro.models import build

    cfg = get_config(ARCH)
    got = {k: getattr(cfg, k) for k in PUBLISHED}
    print(f"model {cfg.name}: {cfg.num_layers}L d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}")
    if got != PUBLISHED:
        print(f"chip_smoke: {ARCH} is not at its published widths: {got}",
              file=sys.stderr)
        return 1
    api = build(cfg)
    t = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(api.init)(jax.random.PRNGKey(args.seed)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    print(f"weights: {n_params} parameters from seed {args.seed} in "
          f"{time.perf_counter() - t:.3f}s")
    rng = np.random.default_rng([args.seed, 1])

    failed = []
    state = {}

    def phase(name, fn):
        print(f"[{name}]", flush=True)
        t0 = time.perf_counter()
        try:
            ok = fn()
        except Exception:
            traceback.print_exc()
            ok = False
        print(f"  -> {'ok' if ok else 'FAILED'} "
              f"({time.perf_counter() - t0:.3f}s)", flush=True)
        if not ok:
            failed.append(name)

    def run_serving():
        gw, prefill, decodes = build_gateway(cfg, params)
        state.update(prefill=prefill, decodes=decodes)
        return serve(cfg, gw, decodes, rng)[0]

    def run_hlo():
        found = decode_hlo_has_kernel(state["decodes"][0])
        print(f"  tpu_custom_call in the compiled paged decode step: {found}")
        return found

    phase("kernel parity", lambda: kernel_parity(cfg, args.seed))
    phase("serving", run_serving)
    if "prefill" in state:
        phase("decode kernel on the path", run_hlo)
        phase("logits parity", lambda: logits_parity(
            cfg, api, params, state["prefill"], rng))
    else:
        failed.append("decode kernel on the path")
        failed.append("logits parity")

    mem = dev.memory_stats() or {}
    print(f"compile: {compile_s[0]:.3f}s in {compile_s[1]} XLA compiles "
          f"(cache lookups included)")
    print(f"HBM peak_bytes_in_use {mem.get('peak_bytes_in_use', 'n/a')} of "
          f"{mem.get('bytes_limit', 'n/a')}")
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
