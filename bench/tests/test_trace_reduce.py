import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Ev

DATA = Path(__file__).resolve().parent / "data"


def _trace():
    """Window [0, 100); device ops overlap and straddle the window; the
    host is in a pump for [0, 60), inside it a decode step [10, 40), then
    idle [60, 100)."""
    t = tr.Trace(devices=[0])
    t.ops = [Ev("fusion.1", -5, 15, 0, "jit_decode_chunk"),     # 0-10 in
             Ev("while.7", 12, 18, 0, "jit_decode_chunk"),      # container
             Ev("custom-call.2", 12, 10, 0, "jit_decode_chunk"),
             Ev("fusion.3", 18, 12, 0, "jit_decode_chunk"),     # overlaps
             Ev("custom-call.1", 50, 5, 0, "jit_kv_quant"),
             Ev("fusion.9", 95, 20, 0, "jit__lambda")]          # cut at 100
    t.modules = [Ev("jit_decode_chunk", -5, 35, 0),
                 Ev("jit_kv_quant", 50, 5, 0),
                 Ev("jit__lambda", 95, 20, 0)]
    t.spans = [Ev("bench.window", 0, 100), Ev("bench.pump", 0, 60),
               Ev("bench.decode_step", 10, 30), Ev("bench.idle", 60, 40)]
    return t


def test_busy_is_the_union_of_operations_in_the_window():
    t = _trace()
    # [0,10) + [12,30) + [50,55) + [95,100) = 10 + 18 + 5 + 5
    assert tr.busy_ns(t, 0, 100) == 38
    assert tr.gaps(t, 0, 100) == [(10, 12), (30, 50), (55, 95)]


def test_program_and_kernel_time_clip_to_the_window():
    red = tr.reduce(_trace())
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(38e-9)
    assert red.module_s["jit_decode_chunk"] == pytest.approx(30e-9)
    assert red.module_s["jit__lambda"] == pytest.approx(5e-9)
    # programs by the host span that holds their start; the decode chunk
    # started before any span
    assert red.span_module_s == {"bench.pump": {
        "jit_kv_quant": pytest.approx(5e-9)}, "bench.idle": {
        "jit__lambda": pytest.approx(20e-9)}}
    assert red.kernel_time("jit_decode_chunk", "custom-call") == \
        pytest.approx(10e-9)
    assert red.kernel_time("jit_kv_quant", "custom-call") == \
        pytest.approx(5e-9)
    assert red.kernel_time("jit_decode_chunk", "while") == 0
    assert red.top_ops(1)[0][0] == "jit_decode_chunk/custom-call.2" or \
        red.top_ops(1)[0][0] == "jit_decode_chunk/fusion.3"


def test_idle_gaps_go_to_the_innermost_host_span():
    red = tr.reduce(_trace())
    # (10,12) mid 11 in decode_step; (30,50) mid 40 in pump (decode_step
    # ends at 40); (55,95) mid 75 in idle
    assert red.idle_span_s == {"bench.decode_step": pytest.approx(2e-9),
                               "bench.pump": pytest.approx(20e-9),
                               "bench.idle": pytest.approx(40e-9)}
    assert tr.span_at(_trace().spans, 200) == tr.NO_SPAN


def test_recorded_chip_trace():
    """A slice of a trace recorded on one TPU v5e (a conversation run of
    mistral-7b-l16): events as ``load`` read them, with the numbers the
    reduction gave when the slice was cut, and the invariants any
    reduction keeps."""
    rec = json.loads((DATA / "v5e_trace_slice.json").read_text())
    t = tr.Trace(devices=rec["devices"])
    t.ops = [Ev(*e) for e in rec["ops"]]
    t.modules = [Ev(*e) for e in rec["modules"]]
    t.spans = [Ev(*e) for e in rec["spans"]]
    red = tr.reduce(t)
    want = rec["expect"]
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    for k, v in want["module_s"].items():
        assert red.module_s[k] == pytest.approx(v, rel=1e-9)
    for k, v in want["idle_span_s"].items():
        assert red.idle_span_s[k] == pytest.approx(v, rel=1e-9)
    assert 0 < red.busy_s <= red.window_s
    assert sum(red.idle_span_s.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    # the kernels the readers look for are there by name
    assert red.kernel_time("jit_decode_chunk", "paged_decode_attention") > 0
    assert any(k.startswith("jit__lambda") for k in red.module_s)


def test_load_reads_host_spans_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.pump"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    t = tr.load(str(path))
    names = {s.name for s in t.spans}
    assert {"bench.window", "bench.pump"} <= names
    lo, hi = tr.window(t)
    pump = next(s for s in t.spans if s.name == "bench.pump")
    assert lo <= pump.start and pump.end <= hi
