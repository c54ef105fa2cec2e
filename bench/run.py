"""Run one cell of the benchmark once, on the chip, and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The run makes the weights on the device from the seed, builds the
deployment its configuration file names, warms the cell's own shapes
(served from the compile cache at ``<checkout>/.jax_cache``), runs the
traffic's lead-in, measures for ``--seconds``, drains up to the traffic's
cap, and then checks the served tokens against the plain reference. With
``--trace 1`` a profiler trace of the window gives the per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), with the numbers compared and their limits under ``checks``,
last. The last lines of standard error give the same comparisons.

It needs a TPU: on any other platform, or with fewer chips than the cell
asks for, it exits non-zero naming what it found, and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"
# JAX's duration event around each XLA compile or compile-cache fetch
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _setup_paths() -> None:
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def use_cache() -> None:
    """Keep every compiled program in the checkout's fixed cache directory
    (the program's entry points read the same variable)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, device, peaks: dict,
             compiles: CompileCounter, hooks=None) -> dict:
    """Build, warm, drive and check one cell; returns the result object.
    ``hooks`` (tests only) may replace the deployment or the served
    tokens to plant a fault under the timed path."""
    import jax
    import numpy as np

    from bench import correct, deploy, loop, model, record, traffic
    from bench import trace_reduce
    from repro.models import registry

    hooks = hooks or {}
    cfg, mix = cell.config, cell.traffic
    k = model.dims(cfg)
    mcfg = model.model_config(cfg)
    parts = {"start_s": time.perf_counter() - t_process}
    params = model.make_weights(cfg, seed)
    model.check_tree(cfg, params, registry.build(mcfg).init)
    parts["weights_s"] = time.perf_counter() - t_process
    dep = deploy.build(mcfg, params, cfg["engine"])
    if "deployment" in hooks:
        hooks["deployment"](dep)
    parts["build_s"] = time.perf_counter() - t_process
    rng = np.random.default_rng([seed, 3])
    deploy.warm_up(dep, traffic.prompt_lengths(mix, seconds), k["V"], rng)
    setup_s = time.perf_counter() - t_process
    parts["compiles"] = compiles.n
    parts["compile_s"] = compiles.seconds

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        log = loop.drive(dep, mix, seed, seconds, k["V"],
                         compiles=lambda: compiles.n, trace_dir=trace_dir,
                         trace_s=seconds)
        reduced = None
        if trace:
            files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            reduced = trace_reduce.reduce(trace_reduce.load(str(files[-1])))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    mem = device.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))

    group = int(dep.decode.cache["slot0"]["kp"].shape[-1]) * 2
    if group != int(cfg["engine"]["kv_group"]):
        raise RuntimeError(f"the program keeps KV in int4 groups of {group}; "
                           f"the configuration states {cfg['engine']['kv_group']}")
    run = record.Run(log=log, seams=dep.log, dims=k, group=group,
                     max_slots=dep.decode.max_slots,
                     peaks=peaks, setup_s=setup_s,
                     trace=reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.reader(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}

    from bench import stats
    n_attempted = len(log.in_window())
    n_failed = len(stats.failed(log))
    done = [correct.Served(np.asarray(s.req.tokens),
                           np.asarray(s.handle.tokens, np.int32))
            for s in log.sent if s.handle.state == "DONE"]
    if "served" in hooks:
        done = hooks["served"](done)
    late = stats.lateness(log)
    info = {"setup": parts, "requests": n_attempted, "finished": len(done),
            "compiles_in_window": log.compiles_t1 - log.compiles_t0,
            "lateness_p95_s": stats.percentile(late, 95),
            "cancelled_at_cap": loop.cancel_open(dep, log)}

    # the program's state goes before the reference runs
    del run, dep, log
    gc.collect()
    info["bytes_in_use_after_free"] = int(
        (device.memory_stats() or {}).get("bytes_in_use", 0))

    sample = correct.choose(done, np.random.default_rng([seed, 5]))
    t_ref = time.perf_counter()
    gap = correct.widest_gap(cfg, params, sample)
    info["reference_s"] = time.perf_counter() - t_ref
    info["reference_tokens"] = int(sum(len(s.tokens) for s in sample))
    limit = float(cell.limits["max_logit_gap"]["limit"])
    ok = bool(sample) and gap == gap and gap <= limit

    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": ok, "attempted": n_attempted, "failed": n_failed,
           "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in
                                           reduced.top_ops(10)],
                            "idle_gaps": [list(x) for x in
                                          reduced.top_idle(10)]}
    out["window"] = info
    out["checks"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: {ROOT} holds no program (src/repro); run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    _setup_paths()
    from bench import spec
    cell = spec.resolve(args.workload, ROOT)
    use_cache()
    import jax
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); found "
              f"{len(devs)} device(s) of platform {devs[0].platform!r} "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 1
    from bench import flops
    peaks = flops.peaks(devs[0].device_kind)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_process=T_PROCESS, device=devs[0], peaks=peaks,
                   compiles=compiles)
    w = out["window"]
    print(f"window: {w['requests']} requests due, {out['failed']} failed, "
          f"{w['finished']} finished; {w['compiles_in_window']} compiles in "
          f"the window; generator lateness p95 {w['lateness_p95_s']}; "
          f"reference {w['reference_s']:.3f}s over {w['reference_tokens']} "
          f"served tokens", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
