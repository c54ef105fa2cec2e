"""The program's own spans and scopes as ``bench.program_trace`` reads
them: a tiny deployment served under the profiler on the CPU, the
readers of ``queue_behind_decode_p95_s`` and ``prefill_token_use``
against the program's spans, the readings without a trace, and a slice
of a trace recorded on one TPU v5e with the scopes its decode chunk's
compiled HLO gives."""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import deploy, loop, model, program_trace, record, spec, stats
from bench import trace_reduce
from bench.program_trace import ProgramSpan, ProgramTrace
from bench.tests.conftest import tiny_config
from bench.trace_reduce import Ev
from repro.serving import tracing
from repro.serving.gateway import ServeRequest

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "mistral-7b-l16.json"
DATA = Path(__file__).resolve().parent / "data"
SEAMS = {"pump", "dispatch", "drain_transfers", "decode", "deliver",
         "prefill", "wire", "transfer", "admit", "page_insert",
         "decode_chunk", "readback", "release"}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Three requests through the gateway of a tiny deployment, under the
    profiler and with the recorder attached: the recorder's spans, the
    trace, and the run as a metric reader sees it (every request due
    when it was submitted, the traced window over the whole serve)."""
    cfg = tiny_config(json.loads(CONFIG.read_text()))
    cfg["engine"]["max_prefill_batch"] = 1
    dep = deploy.build(model.model_config(cfg),
                       model.make_weights(cfg, 2**35 + 3), cfg["engine"])
    rng = np.random.default_rng(1)
    gw, spans = dep.gateway, []
    out = tmp_path_factory.mktemp("trace")
    tracing.record(spans, dep.clock)
    jax.profiler.start_trace(str(out))
    sent = []
    try:
        t0 = dep.clock()
        for i, (n, m) in enumerate([(9, 3), (40, 12), (77, 20)]):
            due = dep.clock()
            h = gw.submit(ServeRequest(100 + i, rng.integers(
                1, 200, n).astype(np.int32), max_new_tokens=m))
            sent.append(loop.Sent(SimpleNamespace(rid=100 + i), due, due, h))
        gw.run_until_drained()
        t1 = dep.clock()
    finally:
        jax.profiler.stop_trace()
        tracing.stop()
    assert all(s.handle.state == "DONE" for s in sent)
    pt = program_trace.load(str(next(out.rglob("*.xplane.pb"))))
    log = loop.RunLog(sent=sent, t_start=t0, due0=t0, due1=t1, t0=t0, t1=t1,
                      t_cap=t1, t_end=t1, tokens_t0=0, tokens_t1=0,
                      trace_t0=t0, trace_t1=t1)
    reduced = trace_reduce.Reduced(window_s=t1 - t0, busy_s=0.0, op_s={},
                                   module_s={}, idle_span_s={}, n_devices=1)
    run = record.Run(log=log, seams=dep.log, dims={}, group=32, max_slots=4,
                     peaks={}, setup_s=0.0, trace=reduced)
    return SimpleNamespace(spans=spans, pt=pt, run=run)


def test_every_seam_is_on_the_host_plane(served):
    spans, pt = served.spans, served.pt
    assert {s.name for s in pt.spans} == {"serve." + n for n in SEAMS}
    assert {s.name for s in spans} == {"serve." + n for n in SEAMS}
    by_seq = {s.seq: s for s in pt.spans}
    for s in pt.spans:
        if s.parent:
            p = by_seq[s.parent]
            assert p.start <= s.start and s.end <= p.end, (s, p)
    rec = {r.seq: r for r in spans}
    assert {s.seq for s in pt.spans} == set(rec)
    assert all(rec[s.seq].parent == s.parent for s in pt.spans)
    # request ids come through as stats, seq as the match key
    pre = [s for s in pt.spans if s.name == "serve.prefill"]
    assert sorted(s.attrs["rids"] for s in pre) == ["[100]", "[101]",
                                                     "[102]"]
    assert sorted(s.attrs["true_tokens"] for s in pre) == [9, 40, 77]


def test_the_recorder_matches_the_trace_clock(served):
    spans, pt = served.spans, served.pt
    off, spread, n = program_trace.match_clock(pt.spans, spans)
    assert n == len(spans)
    assert spread < 1e6                    # ns
    # the recorded span's end lands on the event's end as well
    ends = [abs(r.t1 * 1e9 + off - s.end) for r, s in zip(
        sorted(spans, key=lambda r: r.seq),
        sorted(pt.spans, key=lambda s: s.seq))]
    assert max(ends) < 1e6


def _named(spans, name):
    return [s for s in spans if s.name == "serve." + name]


def test_prefill_token_use_is_the_prefill_spans_share(served):
    """The reader's share equals the one the program's ``serve.prefill``
    spans give: 9, 40 and 77 prompt tokens in buckets of 16, 64, 128."""
    pre = _named(served.spans, "prefill")
    true = sum(s.attrs["true_tokens"] for s in pre)
    padded = sum(s.attrs["bucket_tokens"] for s in pre)
    assert (true, padded) == (126, 16 + 64 + 128)
    got = spec.load_reader("prefill_token_use")(served.run)
    assert got == pytest.approx(100.0 * true / padded)


def test_queue_behind_decode_is_read_off_the_decode_chunks(served):
    """Each request's queued time overlapped by the program's
    ``serve.decode_chunk`` spans; the seam's chunks are the same intervals
    to within the seam's own call overhead."""
    chunks = [(s.t0, s.t1) for s in _named(served.spans, "decode_chunk")]
    assert len(chunks) == len(served.run.seams.chunks)
    log = served.run.log
    behind = []
    for s in log.in_window():
        lo, hi = s.due, s.due + stats.queue_wait(s, log.t_cap)
        behind.append(sum(max(0.0, min(hi, b) - max(lo, a))
                          for a, b in chunks))
    # one prompt a pump: the last request waits behind two decode chunks
    assert max(behind) > 0
    got = spec.load_reader("queue_behind_decode_p95_s")(served.run)
    assert got == pytest.approx(stats.percentile(behind, 95), abs=2e-3)


def test_readings_are_none_with_nothing_to_read(served):
    """No trace, no reading: the run untraced, and a trace whose window
    holds no decode chunk."""
    untraced = record.Run(**{**vars(served.run), "trace": None})
    for name in ("queue_behind_decode_p95_s", "prefill_token_use"):
        assert spec.load_reader(name)(untraced) is None, name
    pt = ProgramTrace(trace_reduce.Trace(spans=[Ev("bench.window", 0, 10)]),
                      [], [])
    assert program_trace.decode_kv_pages_share(pt) is None


@pytest.mark.parametrize("path,scope", [
    ("jit(decode_chunk)/while/body/closed_call/decode/kv_pages/squeeze",
     "decode/kv_pages"),
    ("jit(decode_chunk)/while/body/closed_call/while/body/closed_call/"
     "decode/paged_attn/jit(paged_decode_attention)/pallas_call",
     "decode/paged_attn"),
    ("jit(_lambda)/prefill/dot_general", "prefill"),
    ("jit(decode_chunk)/decode/kv_append/reshape;decode/qkv/add",
     "decode/kv_append"),
    ("jit(scatter)/scatter", ""),
])
def test_scope_of(path, scope):
    assert program_trace.scope_of(path) == scope


def test_hlo_scopes_of_a_compiled_program():
    def f(x):
        with jax.named_scope("decode/kv_pages"):
            y = x * 2
        with jax.named_scope("decode/ffn"):
            return jax.numpy.tanh(y) @ x
    text = jax.jit(f).lower(np.ones((8, 8), np.float32)).compile().as_text()
    scopes = set(program_trace.hlo_scopes(text).values())
    assert {"decode/kv_pages", "decode/ffn"} <= scopes


def _slice():
    rec = json.loads((DATA / "v5e_program_trace_slice.json").read_text())
    ops = [Ev(*e[:5]) for e in rec["ops"]]
    tr = trace_reduce.Trace(ops=ops, devices=rec["devices"],
                            modules=[Ev(*m) for m in rec["modules"]],
                            spans=[Ev(*s) for s in rec["spans"]])
    pt = ProgramTrace(tr, [e[5] for e in rec["ops"]],
                      [ProgramSpan(*s) for s in rec["program_spans"]])
    recorded = [tracing.Span(*r) for r in rec["recorded"]]
    return rec, pt, recorded


def test_recorded_chip_slice():
    """A slice of a trace recorded on one TPU v5e (mistral7b-conversation,
    the program's spans on, op scopes from the decode chunk's compiled
    HLO), with the readings it gave when it was cut."""
    rec, pt, recorded = _slice()
    want = rec["expect"]
    lo, hi = trace_reduce.window(pt.trace)
    scope_s = program_trace.scope_seconds(pt, lo, hi)
    for k, v in want["scope_s"].items():
        assert scope_s[k] == pytest.approx(v, rel=1e-9)
    for k, v in want["idle_program_s"].items():
        assert program_trace.idle_by_program_span(pt, lo, hi)[k] == \
            pytest.approx(v, rel=1e-9)
    off, spread, n = program_trace.match_clock(pt.spans, recorded)
    assert n == len(recorded) and spread == pytest.approx(want["spread_ns"])
    # the kv_pages copies of the trace's top operations sit in their scope
    for e, sc in zip(pt.trace.ops, pt.scopes):
        if e.module == "jit_decode_chunk" and e.name.startswith("squeeze"):
            assert sc == "decode/kv_pages", e
        if e.name.startswith("paged_decode_attention"):
            assert sc == "decode/paged_attn", e
    assert program_trace.scope_time(scope_s, "jit_decode_chunk",
                                    "decode/kv_pages") > 0
    assert program_trace.decode_kv_pages_share(pt) == pytest.approx(
        want["decode_kv_pages_share"], rel=1e-9)
