"""The serving program's own tracing (``repro.serving.tracing``): spans at
the layer seams with the attributes the benchmark reads, the counters
behind ``Gateway.stats()``, named scopes on the device work, and nothing
recorded when no recorder is attached."""
import time

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from bench.program_trace import hlo_scopes
from conftest import admit_many
from repro.configs import get_reduced
from repro.kernels import paged_attention
from repro.models import build
from repro.serving import tracing
from repro.serving.engine import DecodeEngine, GenRequest, PrefillEngine
from repro.serving.gateway import Gateway, SchedulerConfig, ServeRequest

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_reduced("llama-30b")
    return cfg, build(cfg).init(KEY)


@pytest.fixture
def recorded():
    spans = []
    tracing.record(spans, time.perf_counter)
    yield spans
    tracing.stop()


def _reqs(cfg, lens, max_new=8, seed=0):
    rng = np.random.default_rng(seed)
    return [GenRequest(i, rng.integers(1, cfg.vocab_size, int(n)).astype(
        np.int32), max_new_tokens=max_new) for i, n in enumerate(lens)]


def _named(spans, name):
    return [s for s in spans if s.name == "serve." + name]


def test_spans_nest_and_keep_their_attributes(recorded):
    with tracing.span("outer", lambda: {"rids": [3, 4]}):
        with tracing.span("inner", lambda: {"n": 2}):
            pass
        with tracing.span("inner", lambda: {"n": 3}):
            pass
    outer, = _named(recorded, "outer")
    inner = _named(recorded, "inner")
    assert [s.attrs for s in inner] == [{"n": 2}, {"n": 3}]
    assert all(s.parent == outer.seq for s in inner)
    assert outer.parent == 0 and outer.attrs == {"rids": [3, 4]}
    assert outer.t0 <= inner[0].t0 <= inner[1].t1 <= outer.t1
    assert len({s.seq for s in recorded}) == 3
    # profiler stats: an id list stays a string (a leading digit would be
    # read back as one integer)
    assert tracing._stat([3, 4]) == "[3,4]" and tracing._stat(7) == 7


def test_attributes_are_built_only_when_read(recorded):
    """With no recorder and no profiler a span does not build its
    attributes; with a recorder it builds them once."""
    calls = []

    def attrs():
        calls.append(1)
        return {"n": 1}

    tracing.stop()
    with tracing.span("quiet", attrs):
        pass
    assert calls == [] and recorded == []
    tracing.record(recorded, time.perf_counter)
    with tracing.span("heard", attrs):
        pass
    assert calls == [1] and _named(recorded, "heard")[0].attrs == {"n": 1}


def test_no_recorder_records_nothing(small_model):
    cfg, params = small_model
    spans = []
    tracing.record(spans, time.perf_counter)
    tracing.stop()
    assert not TraceAnnotation.is_enabled()
    gw = Gateway([PrefillEngine(cfg, params, max_seq=64)],
                 [DecodeEngine(cfg, params, max_slots=4, max_seq=64,
                               chunk_size=4, paged=True, page_size=8)],
                 backend="ref")
    hs = [gw.submit(ServeRequest(i, r.tokens, max_new_tokens=6))
          for i, r in enumerate(_reqs(cfg, [8, 20]))]
    gw.run_until_drained()
    assert all(h.state == "DONE" for h in hs)
    assert spans == []


def test_prefill_counts_prompt_and_padded_tokens(small_model, recorded):
    """Buckets are powers of two from 16, batches powers of two up to 4:
    [8, 12] run as 2 x 16, [17, 24] as 2 x 32, [40] as 1 x 64, and three
    prompts of one bucket as a batch of 4."""
    cfg, params = small_model
    pre = PrefillEngine(cfg, params, max_seq=64, max_batch=4)
    pre.run(_reqs(cfg, [8, 12, 17, 24, 40]), backend="ref")
    assert (pre.true_tokens, pre.bucket_tokens) == (101, 32 + 64 + 64)
    span, = _named(recorded, "prefill")
    assert span.attrs == {"rids": [0, 1, 2, 3, 4], "batch": 5,
                          "true_tokens": 101, "bucket_tokens": 160}
    pre.run(_reqs(cfg, [5, 6, 7]), backend="ref")
    assert (pre.true_tokens, pre.bucket_tokens) == (101 + 18, 160 + 4 * 16)
    # the wire is built (and trimmed) inside the prefill span
    assert all(w.parent == span.seq for w in _named(recorded, "wire")[:3])


def test_decode_chunk_counts_live_and_walked_pages(small_model, recorded):
    """8-token pages, 4 slots: prompts of 8, 17 and 24 tokens hold 1 + 3 +
    3 pages at the first chunk's start and 2 + 3 + 4 after its 4 steps.
    Each kernel launch copies the pages its keys fill, the token each step
    appends included, and the idle slot's trash page: 2 + 3 + 4 + 1 at
    the first chunk, where the 8- and 24-token contexts end on a page
    boundary, and 2 + 3 + 4 + 1 at the second."""
    cfg, params = small_model
    pre = PrefillEngine(cfg, params, max_seq=64)
    eng = DecodeEngine(cfg, params, max_slots=4, max_seq=64, chunk_size=4,
                       paged=True, page_size=8)
    assert paged_attention.grid_pages([9, 18, 25, 1], 8) == 10
    assert not admit_many(eng, pre.run(_reqs(cfg, [8, 17, 24], 12),
                                       backend="ref"), backend="ref")
    eng.step()
    eng.step()
    first, second = _named(recorded, "decode_chunk")
    assert first.attrs == {"rids": [0, 1, 2], "live_slots": 3, "steps": 4,
                           "live_pages": 7, "grid_pages": 10}
    assert (second.attrs["live_pages"], second.attrs["grid_pages"]) == (9, 10)
    st = eng.page_stats()
    assert (st["decode_chunks"], st["decode_live_pages"],
            st["decode_grid_pages"]) == (2, (7 + 9) * 4, (10 + 10) * 4)
    # the host read-back sits inside its chunk
    assert [r.parent for r in _named(recorded, "readback")] == \
        [first.seq, second.seq]
    admit, = _named(recorded, "admit")
    insert, = _named(recorded, "page_insert")
    assert insert.parent == admit.seq and admit.attrs["rids"] == [0, 1, 2]


def test_gateway_stats_and_pump_spans(small_model, recorded):
    """One prompt a prefill call: 9 tokens in a 16 bucket, 20 in a 32."""
    cfg, params = small_model
    gw = Gateway([PrefillEngine(cfg, params, max_seq=64)],
                 [DecodeEngine(cfg, params, max_slots=4, max_seq=64,
                               chunk_size=4, paged=True, page_size=8)],
                 scheduler=SchedulerConfig(max_prefill_batch=1),
                 backend="ref")
    for i, r in enumerate(_reqs(cfg, [9, 20])):
        gw.submit(ServeRequest(i, r.tokens, max_new_tokens=10))
    gw.run_until_drained()
    st = gw.stats()
    assert (st["counters"]["prefill_true_tokens"],
            st["counters"]["prefill_bucket_tokens"]) == (29, 48)
    chunks = _named(recorded, "decode_chunk")
    pool = st["page_pool"]
    assert pool["decode_chunks"] == len(chunks) > 0
    assert pool["decode_live_pages"] == sum(
        c.attrs["live_pages"] * c.attrs["steps"] for c in chunks)
    assert pool["decode_grid_pages"] == sum(
        c.attrs["grid_pages"] * c.attrs["steps"] for c in chunks)
    pumps = {s.seq for s in _named(recorded, "pump")}
    for name in ("dispatch", "drain_transfers", "decode", "deliver"):
        assert _named(recorded, name)
        assert all(s.parent in pumps for s in _named(recorded, name))
    decodes = {s.seq for s in _named(recorded, "decode")}
    assert all(c.parent in decodes for c in chunks)


def test_decode_ops_sit_under_named_scopes(small_model):
    """Scopes are op metadata only: the program keeps its name."""
    cfg, params = small_model
    eng = DecodeEngine(cfg, params, max_slots=2, max_seq=32, chunk_size=2,
                       paged=True, page_size=8)
    text = eng._chunk.lower(eng.params, eng.cache, eng._host_state(),
                            n_steps=2, eos_id=-1,
                            max_seq=32).compile().as_text()
    assert text.startswith("HloModule jit_decode_chunk")
    for scope in ("decode/weights", "decode/qkv", "decode/kv_append",
                  "decode/kv_pages", "decode/paged_attn", "decode/ffn",
                  "decode/head"):
        assert f"/{scope}/" in text, scope
    pre = PrefillEngine(cfg, params, max_seq=64)
    batch = {"tokens": np.zeros((1, 16), np.int32),
             "last_pos": np.zeros((1,), np.int32),
             "true_len": np.ones((1,), np.int32)}
    text = pre._prefill_fn((1, 16)).lower(params, batch).compile().as_text()
    assert text.startswith("HloModule jit__lambda")
    assert "/prefill/" in text


@pytest.mark.parametrize("path", ["prefill", "prefill_suffix"])
def test_prefill_ops_sit_under_named_scopes(path):
    """A parallel-block prefill (Command-R's kind, tiny): the projections,
    attention, the MLP beside it and the head each under their scope, as
    the benchmark's trace reader names scopes (JAX's loop wrappers
    dropped)."""
    cfg = get_reduced("command-r-35b")
    params = build(cfg).init(KEY)
    pre = PrefillEngine(cfg, params, max_seq=64)
    batch = {"tokens": np.zeros((1, 16), np.int32),
             "last_pos": np.zeros((1,), np.int32),
             "true_len": np.ones((1,), np.int32)}
    if path == "prefill":
        fn = pre._prefill_fn((1, 16))
    else:
        kv = np.zeros((cfg.num_layers, 1, 8, cfg.num_kv_heads,
                       cfg.head_dim), np.float32)
        batch.update(prefix_kv={"slot0": (kv, kv)},
                     prefix_len=np.full((1,), 8, np.int32))
        fn = pre._suffix_fn((1, 16, 8))
    text = fn.lower(params, batch).compile().as_text()
    assert text.startswith("HloModule jit__lambda")
    scopes = set(hlo_scopes(text).values())
    for scope in ("prefill/qkv", "prefill/attn", "prefill/ffn",
                  "prefill/head"):
        assert scope in scopes, scope
