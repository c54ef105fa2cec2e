"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. ``--quick`` trims durations.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only slo,throughput]
"""
import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--suite", default="",
                    help="alias for --only (e.g. --suite throughput; the "
                         "throughput suite also writes BENCH_throughput.json)")
    args, _ = ap.parse_known_args()

    from benchmarks import common  # noqa: F401  (puts src/ on sys.path)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_case_study, bench_continuous_batching,
                            bench_fault_tolerance, bench_kernels,
                            bench_kv_compression, bench_network_effect,
                            bench_paged_kv, bench_prefix_cache,
                            bench_ratio_sweep, bench_rescheduling,
                            bench_scheduling_time, bench_serving_api,
                            bench_simulator_accuracy, bench_slo_attainment,
                            bench_throughput)

    suites = {
        "slo": (bench_slo_attainment, "Fig 7-8 SLO attainment"),
        "throughput": (bench_throughput, "Fig 9 throughput"),
        "serving_api": (bench_serving_api,
                        "gateway lifecycle TTFT/TPOT/goodput per transport"),
        "sched_time": (bench_scheduling_time, "Fig 10 scheduling time"),
        "rescheduling": (bench_rescheduling,
                         "Fig 11/Table 4 rescheduling (sim + live flip)"),
        "paged_kv": (bench_paged_kv,
                     "paged int4-resident KV: capacity + tok/s vs dense"),
        "prefix_cache": (bench_prefix_cache,
                         "prefix-sharing KV: Zipf hit rate, warm TTFT, "
                         "capacity vs no-sharing"),
        "continuous_batching": (bench_continuous_batching,
                                "chunked prefill vs one-shot: interactive "
                                "TTFT p99 under a long-prompt burst"),
        "fault_tolerance": (bench_fault_tolerance,
                            "chaos crash+preemption: SLO attainment vs "
                            "no-handling baseline"),
        "kvcomp": (bench_kv_compression, "Fig 12/18, Tables 2/8 KV comp"),
        "ratio": (bench_ratio_sweep, "Fig 6/14 prefill:decode ratio"),
        "network": (bench_network_effect, "Table 5 network effect"),
        "sim_acc": (bench_simulator_accuracy, "Fig 19 simulator accuracy"),
        "case": (bench_case_study, "Table 3 case study"),
        "kernels": (bench_kernels, "kernel micro + v5e roofline"),
    }
    aliases = {"resched": "rescheduling",     # legacy suite names
               "faults": "fault_tolerance"}
    only = {aliases.get(s, s)
            for s in f"{args.only},{args.suite}".split(",") if s}
    unknown = only - suites.keys()
    if unknown:
        sys.exit(f"unknown suite(s): {sorted(unknown)}; "
                 f"known: {sorted(suites)}")
    print("name,us_per_call,derived")
    failures = 0
    for key, (mod, desc) in suites.items():
        if only and key not in only:
            continue
        t0 = time.perf_counter()
        try:
            for r in mod.run(quick=args.quick):
                print(r, flush=True)
            print(f"# {key} ({desc}): {time.perf_counter()-t0:.1f}s", flush=True)
        except Exception as e:
            failures += 1
            print(f"# {key} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
