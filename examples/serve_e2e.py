"""End-to-end phase-split serving through the v2 gateway API.

Runs REAL model computation on CPU: a prefill engine and two decode engines
(reduced-config LLaMA-30B family), int4-quantized KV transfer between them,
continuous batching on decode — driven OPEN-LOOP: requests are submitted at
Poisson arrival times against the wall clock, tokens stream back through
``RequestHandle`` callbacks while later requests are still arriving, and
per-request TTFT / TPOT / E2E deadlines are accounted.

  PYTHONPATH=src python examples/serve_e2e.py [--requests 12] [--rate 3] \\
      [--transport sim] [--no-compress]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import get_reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models import build
from repro.serving.engine import DecodeEngine, PrefillEngine
from repro.serving.gateway import (DONE, Gateway, SchedulerConfig,
                                   ServeRequest, drive_open_loop,
                                   summarize_handles, warmup_gateway)
from repro.serving.transport import InProcessTransport, SimNetworkTransport


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--arch", default="llama-30b")
    ap.add_argument("--rate", type=float, default=3.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--transport", choices=("inproc", "sim"),
                    default="inproc")
    ap.add_argument("--ttft-slo", type=float, default=0.0,
                    help="per-request TTFT deadline in s (0 = none)")
    ap.add_argument("--e2e-slo", type=float, default=0.0,
                    help="per-request E2E deadline in s (0 = none)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged decode cache)")
    ap.add_argument("--pages", type=int, default=0,
                    help="page-pool budget per decode engine (0 = parity "
                         "with the dense max_slots x max_seq budget)")
    ap.add_argument("--no-paged", action="store_true",
                    help="use the dense slotted decode cache instead of "
                         "the paged int4-resident pool")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable the radix prefix cache (refcounted "
                         "copy-on-write page sharing + prefill skip)")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="prefill chunk token budget per scheduler tick "
                         "(0 = one-shot prefill); with chunking, a long "
                         "prompt no longer head-of-line-blocks the TTFT "
                         "of short prompts behind it")
    ap.add_argument("--decode-chunk-steps", type=int, default=0,
                    help="decode steps per scheduler tick (0 = engine "
                         "default)")
    ap.add_argument("--max-prefill-batch", type=int, default=4,
                    help="max prompts per prefill dispatch/chunk tick")
    ap.add_argument("--chaos", action="store_true",
                    help="inject a decode-replica crash and a spot "
                         "preemption mid-trace (3 decode replicas so a "
                         "survivor remains; every request must still "
                         "finish)")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_reduced(args.arch)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    print(f"model {cfg.name}: {cfg.num_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab_size}")

    prefill = PrefillEngine(cfg, params, max_seq=128)
    n_dec = 3 if args.chaos else 2
    decodes = [DecodeEngine(cfg, params, max_slots=4, max_seq=128,
                            paged=not args.no_paged,
                            page_size=args.page_size,
                            num_pages=args.pages or None,
                            prefix_sharing=not (args.no_paged
                                                or args.no_prefix_sharing))
               for _ in range(n_dec)]
    if decodes[0].paged_fallback:
        print(f"note: {decodes[0].paged_fallback}")
    if args.transport == "sim":
        # shared-ethernet-class link, full-model wire bytes (the reduced
        # engine computes, the FULL model's KV crosses the network)
        transport = SimNetworkTransport(alpha=5e-3, bandwidth=0.6e9,
                                        bytes_scale=400.0)
    else:
        transport = InProcessTransport()
    gw = Gateway([prefill], decodes, transport=transport,
                 scheduler=SchedulerConfig(
                     prefill_chunk_tokens=args.chunk_tokens,
                     max_prefill_batch=args.max_prefill_batch,
                     decode_chunk_steps=args.decode_chunk_steps),
                 compress=not args.no_compress, backend="auto")

    print("warming up jit caches...")
    warmup_gateway(gw, cfg.vocab_size, prompt_lens=(16, 24, 32))

    # open-loop Poisson trace: every prompt opens with a shared 16-token
    # "system prompt" (page-aligned — partial radix hits once the first
    # request donates its chain), and ~1/3 of requests repeat an earlier
    # prompt verbatim (full hits: prefill skipped entirely). Seed differs
    # from warmup_engines' rng(0) so the warmup donations don't collide
    # with the trace prompts.
    rng = np.random.default_rng(7)
    sys_prefix = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
    arrivals, prompts = [], []
    t = 0.0
    for rid in range(args.requests):
        t += rng.exponential(1.0 / args.rate)
        if prompts and rng.random() < 0.35:
            toks = prompts[int(rng.integers(len(prompts)))]
        else:
            n_in = int(rng.choice([16, 24, 32]))
            toks = np.concatenate([sys_prefix, rng.integers(
                1, cfg.vocab_size, n_in - 16).astype(np.int32)])
            prompts.append(toks)
        arrivals.append((t, ServeRequest(
            rid, toks, max_new_tokens=args.max_new,
            ttft_deadline_s=args.ttft_slo or float("inf"),
            e2e_deadline_s=args.e2e_slo or float("inf"))))

    ctl = None
    if args.chaos:
        from repro.serving.faults import (CRASH, PREEMPT, FaultEvent,
                                          FaultSchedule, install_chaos)
        span = args.requests / args.rate
        schedule = FaultSchedule([
            FaultEvent(t=0.3 * span, kind=CRASH, phase="decode", idx=-1,
                       require_busy=True),
            FaultEvent(t=0.55 * span, kind=PREEMPT, phase="decode", idx=-1,
                       grace_s=0.75, require_busy=True)])
        ctl = install_chaos(gw, schedule)
        print(f"chaos armed: decode crash at ~{0.4*span:.1f}s, spot "
              f"preemption (grace 0.75s) at ~{0.7*span:.1f}s")

    t0 = time.time()
    first_seen = {}

    def on_token(h, tok):
        if h.request.rid not in first_seen:
            first_seen[h.request.rid] = time.time() - t0
            if len(first_seen) <= 4:
                print(f"  rid {h.request.rid}: first token streamed at "
                      f"+{first_seen[h.request.rid]*1e3:.0f}ms "
                      f"({h.state}, {len(gw.queue)} queued behind it)")

    print(f"serving {args.requests} requests open-loop at "
          f"{args.rate:.1f} req/s ({args.transport} transport)...")
    handles = drive_open_loop(gw, arrivals, on_token=on_token)
    wall = time.time() - t0

    s = summarize_handles(handles)
    print(f"\nstates: {s['states']}  "
          f"({s['n_done']}/{s['n_submitted']} done, "
          f"{s['tokens']} tokens in {wall:.2f}s = "
          f"{s['tokens']/wall:.1f} tok/s)")
    print(f"TTFT  p50={s['ttft_p50_s']*1e3:.0f}ms "
          f"p99={s['ttft_p99_s']*1e3:.0f}ms")
    print(f"TPOT  p50={s['tpot_p50_s']*1e3:.0f}ms")
    print(f"E2E   p50={s['e2e_p50_s']*1e3:.0f}ms "
          f"p99={s['e2e_p99_s']*1e3:.0f}ms")
    print(f"goodput (deadline attainment): {s['goodput']*100:.0f}%")
    kv = "int4" if not args.no_compress else "raw bf16"
    print(f"KV transfer wire format: {kv}")
    if isinstance(transport, SimNetworkTransport):
        print(f"sim network: {transport.transfers} transfers, "
              f"{transport.bytes_sent/1e6:.1f}MB, "
              f"mean hop {transport.mean_delay_s*1e3:.1f}ms")
    syncs = sum(d.host_syncs for d in decodes)
    steps = sum(d.steps_run for d in decodes)
    print(f"decode host syncs: {syncs} for {steps} device steps "
          f"({steps / max(syncs, 1):.1f} steps/sync)")
    if decodes[0].paged:
        for i, d in enumerate(decodes):
            st = d.page_stats()
            print(f"decode {i} page pool: {st['pages']} pages x "
                  f"{st['page_size']} tok, peak {st['peak_in_use']} in use, "
                  f"{st['zero_copy_inserts']} zero-dequant wire inserts "
                  f"({st['reencoded_inserts']} re-encoded), "
                  f"{st['alloc_failures']} admission stalls")
    st = gw.stats()
    c = st["counters"]
    print(f"gateway stats: epoch={st['epoch']} retries={c['retries']} "
          f"requeues={c['requeues']} migrations={c['migrations']} "
          f"(tokens={c['migrated_tokens']}) "
          f"preemptions={c['preemptions']} failed={c['failed']}")
    if args.chunk_tokens > 0:
        print(f"chunked prefill: {c['chunk_ticks']} chunk ticks, "
              f"{c['chunked_prefills']} prompts chunked "
              f"(budget {args.chunk_tokens} tok/tick)")
    if st["page_pool"]:
        print(f"page pool (fleet): "
              f"{st['page_pool']['alloc_failures']:.0f} admission stalls, "
              f"{st['page_pool']['in_use']:.0f} pages still in use")
    pfx = st["prefix"]
    if pfx["hits"] or pfx["partial_hits"] or pfx["misses"]:
        pool = st["page_pool"] or {}
        print(f"prefix cache: {pfx['hits']} full hits (prefill skipped), "
              f"{pfx['partial_hits']} partial (suffix prefill), "
              f"{pfx['misses']} misses "
              f"(hit rate {pfx['hit_rate']*100:.0f}%, "
              f"{pfx['hit_tokens']} prompt tokens reused, "
              f"{pool.get('cow_copies', 0):.0f} COW copies)")
    print("replicas:", "  ".join(
        f"{r['phase']}:{r['idx']}={r['status']}"
        + (f"({r['suspect_why']})" if r["suspect_why"] else "")
        for r in st["replicas"]))
    if ctl is not None:
        print("chaos fired:", [
            {k: f.get(k) for k in ("kind", "idx", "migrated", "requeued")
             if k in f} for f in ctl.fired])
    if gw.events:
        print("events:", gw.events[:5])
    n_done = s["states"].get(DONE, 0)
    if n_done < args.requests:
        print(f"FAILED: only {n_done}/{args.requests} requests finished")
        sys.exit(1)


if __name__ == "__main__":
    main()
