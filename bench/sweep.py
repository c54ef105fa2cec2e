"""Find a Poisson cell's knee once, by a sweep of arrival rates on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1,2,3,4

One process builds and warms the cell, then serves the cell's traffic at
each rate in turn (lead-in, window, drain) and prints, per rate, the
request and token rates completed in the window, the tails, and how many
requests were still waiting when the window closed. The knee is the
highest rate whose completions keep up with its arrivals without a
growing queue; a cell then offers load at 0.8 x the knee. Benchmark runs
never sweep.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    from bench import run as brun
    from bench import spec
    cell = spec.resolve(args.workload)
    brun.use_cache()
    import jax
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"sweep: needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 1
    from bench import deploy, loop, model, stats, traffic
    cfg = cell.config
    k = model.dims(cfg)
    params = model.make_weights(cfg, args.seed)
    dep = deploy.build(model.model_config(cfg), params, cfg["engine"])
    lengths = set()
    for r in rates:
        mix = copy.deepcopy(cell.traffic)
        mix["arrival"]["rate"] = r
        lengths |= set(traffic.prompt_lengths(mix, args.seconds))
    deploy.warm_up(dep, sorted(lengths), k["V"],
                   np.random.default_rng([args.seed, 3]))
    print(f"set-up {time.perf_counter() - T_PROCESS:.1f}s", file=sys.stderr)
    for r in rates:
        mix = copy.deepcopy(cell.traffic)
        mix["arrival"]["rate"] = r
        log = loop.drive(dep, mix, args.seed, args.seconds, k["V"])
        win = log.in_window()
        done_in = [s for s in log.sent if s.handle.state == "DONE"
                   and log.t0 <= s.handle.t_done < log.t1]
        waiting = [s for s in log.sent if s.due < log.t1 and (
            s.handle.t_first < 0 or s.handle.t_first >= log.t1)]
        row = {"rate": r, "due_in_window": len(win),
               "done_per_s": len(done_in) / log.window_s,
               "tokens_per_s": stats.tokens_per_s(log),
               "waiting_at_close": len(waiting),
               "ttft_p50_s": stats.percentile(stats.ttfts(log), 50),
               "ttft_p95_s": stats.percentile(stats.ttfts(log), 95),
               "tpot_p50_s": stats.percentile(stats.tpots(log), 50),
               "tpot_p95_s": stats.percentile(stats.tpots(log), 95),
               "failed": len(stats.failed(log)),
               "compiles_in_window": log.compiles_t1 - log.compiles_t0}
        loop.cancel_open(dep, log)
        dep.gateway.clear_finished()
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
