"""Readings that a cell's correctness limit is set from (not a benchmark
run). For each seed, in one process:

* the program: a run of the cell as the benchmark makes it (the timed
  path at the cell's sizes and load; its whole result is printed too), and
  the widest logit gap of its sample of served tokens under the plain
  reference;
* the control: on the same sample, the token the reference with float8
  matrices puts first at each served position, and that token's widest
  gap under the full-precision reference.

    python bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3

Prints one JSON line per seed. The limit lies above the program's largest
reading and below the control's smallest (``bench/limits/<cell>.json``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def control_readings(cell, seed: int, seconds: float, *, device, peaks,
                     compiles, t_process: float) -> dict:
    import numpy as np

    from bench import correct, model
    from bench import run as brun
    captured = {}

    def keep(done):
        captured["done"] = done
        return done

    out = brun.run_cell(cell, seed, seconds, False, t_process=t_process,
                        device=device, peaks=peaks, compiles=compiles,
                        hooks={"served": keep})
    import gc
    gc.collect()
    sample = correct.choose(captured["done"],
                            np.random.default_rng([seed, 5]))
    params = model.make_weights(cell.config, seed)
    prog, ctrl = 0.0, 0.0
    for s in sample:
        g, c = correct.gaps(cell.config, params, s, control=True)
        prog, ctrl = max(prog, float(g.max())), max(ctrl, float(c.max()))
    del params
    return {"seed": seed, "program_gap": prog, "control_gap": ctrl,
            "run_gap": out["checks"]["max_logit_gap"]["value"],
            "sample_tokens": int(sum(len(s.tokens) for s in sample)),
            "run": out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import gc

    from bench import flops, spec
    from bench import run as brun
    cell = spec.resolve(args.workload)
    brun.use_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    compiles = brun.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        row = control_readings(cell, seed, args.seconds, device=dev,
                               peaks=flops.peaks(dev.device_kind),
                               compiles=compiles, t_process=t)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
