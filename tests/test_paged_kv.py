"""Paged int4-resident decode KV cache (DESIGN.md §7).

Covers: interpret-mode parity of the fused-dequant ``paged_decode_attention``
kernel vs the dense oracle (ragged lengths, page-boundary-straddling
sequences, int4 vs bf16 residency), ``PagePool`` alloc/free invariants
under hypothesis, the engine-level page lifecycle (release mid-stream
returns every page; finish returns every page; admission is page-budget
gated), zero-dequant wire insertion, phase-flip pool ownership, and the
cost model's page-budget capacity term.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import admit_many, admit_one

from repro.configs import get_reduced
from repro.kernels import ops, ref
from repro.models import build, paged
from repro.serving import page_pool
from repro.serving.engine import DecodeEngine, GenRequest, PrefillEngine, \
    Replica
from repro.serving.page_pool import PagePool, pages_needed

KEY = jax.random.PRNGKey(0)


# -- kernel parity -----------------------------------------------------------


class Lens(list):
    """Valid lengths of a kernel case, with the slots left ``idle`` on the
    trash page (page-table rows of zeros, as the engine leaves a free
    slot) and the leading pages slot 1 ``share``s with slot 0 (a prefix
    chain both read)."""

    def __init__(self, lens, idle=(), share=0):
        super().__init__(lens)
        self.idle, self.share = idle, share


def _paged_fixture(B, Hkv, gq, hd, page_size, kv_len, *, seed=0,
                   resident="int4"):
    """Random dense K/V scattered into a shuffled page pool; returns
    (q, k_pages, v_pages, page_table, dense_k, dense_v) where dense_* are
    the values attention should see (dequantized for int4 residency)."""
    rng = np.random.default_rng(seed)
    B_ = len(kv_len)
    assert B_ == B
    idle = getattr(kv_len, "idle", ())
    share = getattr(kv_len, "share", 0)
    W = max(-(-int(l) // page_size) for l in kv_len)
    g = next(gg for gg in (128, 64, 32, 16, 8, 4, 2)
             if (Hkv * hd) % gg == 0)
    ppr = (Hkv * hd) // g
    P = 1 + sum(-(-int(l) // page_size) for l in kv_len)
    pt = np.zeros((B, W), np.int32)
    perm = rng.permutation(np.arange(1, P))
    n = 0
    for b in range(B):
        if b in idle:
            continue
        k = -(-int(kv_len[b]) // page_size)
        own = share if b == 1 else 0
        pt[b, :own] = pt[0, :own]
        pt[b, own:k] = perm[n:n + k - own]
        n += k - own
    S = W * page_size
    K = rng.standard_normal((B, S, Hkv, hd)).astype(np.float32)
    V = rng.standard_normal((B, S, Hkv, hd)).astype(np.float32)
    K[1, :share * page_size] = K[0, :share * page_size]
    V[1, :share * page_size] = V[0, :share * page_size]

    def to_pages(X):
        if resident == "bf16":
            buf = np.zeros((P, page_size, Hkv, hd), np.float32)
            for b in range(B):
                for t in range(int(kv_len[b])):
                    buf[pt[b, t // page_size], t % page_size] = X[b, t]
            pages = jnp.asarray(buf, jnp.bfloat16)
            dense = np.zeros_like(X)
            for b in range(B):
                for t in range(int(kv_len[b])):
                    dense[b, t] = np.asarray(
                        pages[pt[b, t // page_size], t % page_size],
                        np.float32)
            return pages, dense
        kp = np.zeros((P, page_size * ppr, g // 2), np.uint8)
        ks = np.zeros((P, page_size * ppr, 1), np.float32)
        kz = np.zeros((P, page_size * ppr, 1), np.float32)
        dense = np.zeros_like(X)
        for b in range(B):
            for t in range(int(kv_len[b])):
                rows = X[b, t].reshape(ppr, g)
                p_, s_, z_ = ref.kv_quant_ref(jnp.asarray(rows))
                page, r0 = pt[b, t // page_size], (t % page_size) * ppr
                kp[page, r0:r0 + ppr] = np.asarray(p_)
                ks[page, r0:r0 + ppr] = np.asarray(s_)
                kz[page, r0:r0 + ppr] = np.asarray(z_)
                dense[b, t] = np.asarray(ref.kv_dequant_ref(
                    p_, s_, z_, dtype=jnp.float32)).reshape(Hkv, hd)
        return (jnp.asarray(kp), jnp.asarray(ks), jnp.asarray(kz)), dense

    k_pages, dense_k = to_pages(K)
    v_pages, dense_v = to_pages(V)
    q = jnp.asarray(rng.standard_normal((B, Hkv, gq, hd)), jnp.float32)
    return q, k_pages, v_pages, jnp.asarray(pt), dense_k, dense_v


# ragged + page-straddling: lengths deliberately off page boundaries, one
# exactly on a boundary, one inside the first page
KERNEL_CASES = [
    # (B, Hkv, gq, hd, page_size, kv_lens)
    (3, 2, 4, 16, 8, [5, 16, 23]),
    (2, 1, 8, 32, 16, [17, 48]),          # MQA, boundary-exact second seq
    (4, 4, 1, 16, 8, [1, 9, 24, 31]),     # MHA decode (gq=1)
    # Mistral-7B's heads (g 128, ppr 8), 8 pages (128 keys) a step: an
    # idle slot on the trash page, a block boundary and one key past it
    (3, 8, 4, 128, 16, Lens([1, 128, 129], idle=(0,))),
    # 16 pages a step: two whole blocks, the first slot filling its table
    (3, 2, 4, 16, 8, Lens([256, 128, 129])),
    # slot 1 reads slot 0's first 5 pages (a shared prefix chain)
    (3, 2, 4, 16, 8, Lens([80, 150, 7], share=5)),
]


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("B,Hkv,gq,hd,ps,lens", KERNEL_CASES)
@pytest.mark.parametrize("resident", ["int4", "bf16"])
def test_paged_attention_matches_dense_ref(backend, B, Hkv, gq, hd, ps,
                                           lens, resident):
    """The fused-dequant paged kernel must equal the dense oracle run over
    the SAME (dequantized) values — bf16-level tolerance."""
    kv_len = jnp.asarray(lens, jnp.int32)
    q, kpg, vpg, pt, dk, dv = _paged_fixture(B, Hkv, gq, hd, ps, lens,
                                             resident=resident)
    out = ops.paged_decode_attention(q, kpg, vpg, pt, kv_len,
                                     page_size=ps, backend=backend)
    want = ref.decode_attention_ref(
        q, jnp.asarray(dk.transpose(0, 2, 1, 3)),
        jnp.asarray(dv.transpose(0, 2, 1, 3)), kv_len=kv_len)
    tol = 2e-2 if resident == "bf16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_paged_attention_ignores_trash_and_masked_tail(backend):
    """Positions beyond kv_len (the tail of the last page), table entries
    pointing at the trash page, and table entries past a slot's length
    that name pages another slot owns must not leak into the output."""
    lens = [5, 16, 23]
    kv_len = jnp.asarray(lens, jnp.int32)
    q, kpg, vpg, pt, dk, dv = _paged_fixture(3, 2, 4, 16, 8, lens, seed=4)
    # slots 0 and 1 keep stale entries past their lengths: slot 2's pages
    pt = pt.at[0, 1:].set(pt[2, 1:]).at[1, 2].set(pt[2, 0])
    run = lambda k, v: np.asarray(ops.paged_decode_attention(
        q, k, v, pt, kv_len, page_size=8, backend=backend))
    out1 = run(kpg, vpg)
    # poison the trash page, every masked tail row and slot 2's pages
    ppr = kpg[0].shape[1] // 8
    tails = [(int(pt[b, l // 8]), (l % 8) * ppr)
             for b, l in enumerate(lens) if l % 8]

    def poison(pages):
        out = []
        for a, bad in zip(pages, (255, 1e6, -1e6)):
            a = a.at[0].set(bad).at[pt[2]].set(bad)
            for page, row in tails:
                a = a.at[page, row:].set(bad)
            out.append(a)
        return tuple(out)
    out2 = run(poison(kpg), poison(vpg))
    np.testing.assert_array_equal(out1[:2], out2[:2])
    assert np.isfinite(out2[:2]).all()


def test_paged_schedule_copies_the_counted_pages():
    """The ragged grid's schedule: a step per block of context (one at
    least a slot), and a page past a slot's context repeating what its
    operand read last, so the pipeline copies ``grid_pages`` pages, plus
    the trash page for each operand the first slot's block leaves
    empty. Distinct pages, so no copy is skipped by chance."""
    from repro.kernels import paged_attention as pa
    ps, ppb, W = 8, 4, 12
    lens = [3, 1, 33, 96, 0, 32]
    n_live = [-(-n // ps) for n in lens]
    pt = np.zeros((len(lens), W), np.int32)
    nxt = 1
    for b, n in enumerate(n_live):
        pt[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    n_steps, slot, blk, page = pa._schedule(
        jnp.asarray(pt), jnp.asarray(lens, jnp.int32), page_size=ps,
        ppb=ppb)
    n_steps = int(n_steps)
    assert n_steps == 1 + 1 + 2 + 3 + 1 + 1
    page = np.asarray(page).reshape(ppb, -1).T[:n_steps]
    copies = 1 + (page[1:] != page[:-1]).sum(0)
    # operand 0 reads a page in every step but the empty slot's; the
    # others copy the trash page first, then what slots 2, 3 and 5 fill
    assert copies.tolist() == [8, 6, 6, 6]
    assert copies.sum() == pa.grid_pages(lens, ps) + ppb - n_live[0]
    # every page of context is read, in its slot's steps, at its place
    slot, blk = np.asarray(slot)[:n_steps], np.asarray(blk)[:n_steps]
    for b, n in enumerate(n_live):
        for p in range(n):
            t, = np.nonzero((slot == b) & (blk == p // ppb))
            assert page[t[0], p % ppb] == pt[b, p]


# -- PagePool invariants ------------------------------------------------------


def test_page_pool_basics():
    pool = PagePool(9, 8)
    assert pool.capacity == 8 and pool.n_free == 8
    a = pool.alloc(3, owner=0)
    b = pool.alloc(5, owner=1)
    assert a is not None and b is not None
    assert 0 not in a + b, "trash page must never be handed out"
    assert set(a).isdisjoint(b)
    assert pool.alloc(1, owner=2) is None and pool.alloc_failures == 1
    pool.free(a)
    assert pool.n_free == 3
    with pytest.raises(ValueError):
        pool.free(a)            # double free
    assert pool.owned_by(1) == sorted(b)


def test_page_pool_invariants_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.tuples(st.booleans(), st.integers(0, 6)),
                    min_size=1, max_size=60))
    def scenario(script):
        pool = PagePool(17, 4)
        live = {}
        for i, (is_alloc, n) in enumerate(script):
            if is_alloc or not live:
                got = pool.alloc(n, owner=i)
                if n == 0:
                    assert got == []
                elif got is None:
                    assert n > pool.n_free or pool.n_free == 0 \
                        or n > 16 - sum(map(len, live.values()))
                else:
                    assert len(got) == n and 0 not in got
                    for other in live.values():
                        assert set(got).isdisjoint(other)
                    if got:
                        live[i] = got
            else:
                key = next(iter(live))
                pool.free(live.pop(key))
            in_use = sum(len(v) for v in live.values())
            assert pool.n_in_use == in_use
            assert pool.n_free == pool.capacity - in_use
            assert pool.allocs - pool.frees == in_use

    scenario()


def test_pages_needed():
    assert pages_needed(0, 8) == 1      # a slot always owns >= 1 page
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2
    assert pages_needed(64, 16) == 4


# -- engine lifecycle ---------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = get_reduced("llama-30b")
    api = build(cfg)
    params = api.init(KEY)
    return cfg, api, params


def _reqs(cfg, lens, max_new=8, seed=0):
    rng = np.random.default_rng(seed)
    return [GenRequest(i, rng.integers(1, cfg.vocab_size,
                                       int(l)).astype(np.int32),
                       max_new_tokens=max_new)
            for i, l in enumerate(lens)]


def test_paged_chunked_matches_paged_reference(small_model):
    """The jitted multi-token scan over the pool must reproduce the
    per-step paged path token for token (same wires in, same tokens out)
    — the ``step_reference`` parity oracle for the paged engine."""
    cfg, api, params = small_model
    pre = PrefillEngine(cfg, params, max_seq=64)
    lens = [8, 12, 17, 24]
    kw = dict(max_slots=4, max_seq=64, paged=True, page_size=8)
    a = DecodeEngine(cfg, params, chunk_size=8, **kw)
    b = DecodeEngine(cfg, params, **kw)
    for r, w, f in pre.run(_reqs(cfg, lens, 12), backend="ref"):
        assert admit_one(a, r, f, wire=w, backend="ref")
    for r, w, f in pre.run(_reqs(cfg, lens, 12), backend="ref"):
        assert admit_one(b, r, f, wire=w, backend="ref")
    done_a, done_b = [], []
    while a.active:
        done_a += a.step()
    while b.active:
        done_b += b.step_reference()
    toks_a = {r.rid: r.out_tokens for r in done_a}
    toks_b = {r.rid: r.out_tokens for r in done_b}
    assert toks_a == toks_b
    assert all(len(t) == 12 for t in toks_a.values())


def test_paged_finish_returns_all_pages(small_model):
    cfg, api, params = small_model
    pre = PrefillEngine(cfg, params, max_seq=64)
    eng = DecodeEngine(cfg, params, max_slots=4, max_seq=64, chunk_size=4,
                       paged=True, page_size=8)
    total = eng.pool.n_free
    for r, w, f in pre.run(_reqs(cfg, [8, 17, 24], 6), backend="ref"):
        assert admit_one(eng, r, f, wire=w, backend="ref")
    assert eng.pool.n_in_use > 0
    while eng.active:
        eng.step()
    assert eng.pool.n_free == total and eng.pool.n_in_use == 0
    assert np.all(np.asarray(eng.cache["page_table"]) == 0)
    assert np.all(np.asarray(eng.cache["lengths"]) == 0)


def test_paged_release_mid_stream_returns_every_page(small_model):
    """A cancellation mid-decode must return the slot's ENTIRE page list
    to the pool and point its table row back at the trash page."""
    cfg, api, params = small_model
    pre = PrefillEngine(cfg, params, max_seq=64)
    eng = DecodeEngine(cfg, params, max_slots=2, max_seq=64, chunk_size=2,
                       paged=True, page_size=8)
    total = eng.pool.n_free
    (r, w, f), = pre.run(_reqs(cfg, [20], max_new=30), backend="ref")
    assert admit_one(eng, r, f, wire=w, backend="ref")
    held = len(eng.pool.owned_by(0))
    assert held == pages_needed(20 + 30, 8)
    eng.step()                              # mid-stream
    assert eng.release(0) is r
    assert eng.pool.n_free == total and eng.pool.n_in_use == 0
    assert np.all(np.asarray(eng.cache["page_table"][0]) == 0)
    # the freed budget is immediately re-admissible
    (r2, w2, f2), = pre.run(_reqs(cfg, [40], max_new=16, seed=3),
                            backend="ref")
    assert admit_one(eng, r2, f2, wire=w2, backend="ref")


def test_paged_admission_is_page_budget_gated(small_model):
    """With plenty of slots but a tiny pool, admission rejects the tail
    on pages, and free_slots() (what the gateway's dispatch reads) is
    truncated by the page budget."""
    cfg, api, params = small_model
    pre = PrefillEngine(cfg, params, max_seq=64)
    eng = DecodeEngine(cfg, params, max_slots=8, max_seq=64, paged=True,
                       page_size=8, num_pages=7)      # 6 usable pages
    wires = pre.run(_reqs(cfg, [20, 20, 20], max_new=12), backend="ref")
    rejected = admit_many(eng, wires, backend="ref")
    # each request needs ceil(32/8) = 4 pages; only one fits in 6
    assert len(rejected) == 2
    assert eng.active == 1
    assert eng.pool.alloc_failures >= 1
    assert len(eng.free_slots()) == 0, \
        "page budget exhausted: no admissible slot despite 7 free slots"
    while eng.active:
        eng.step()
    assert len(eng.free_slots()) >= 1


def test_paged_zero_dequant_inserts_from_bucketed_wire(small_model):
    """Bucketed-prefill wires carry position-aligned int4 groups: they
    must scatter into pages with NO dequant round-trip."""
    cfg, api, params = small_model
    pre = PrefillEngine(cfg, params, max_seq=64)        # bucketed
    assert pre.bucketed
    eng = DecodeEngine(cfg, params, max_slots=4, max_seq=64, paged=True,
                       page_size=8)
    for r, w, f in pre.run(_reqs(cfg, [9, 17], 4), backend="ref"):
        assert admit_one(eng, r, f, wire=w, backend="ref")
    assert eng.zero_copy_inserts > 0
    assert eng.reencoded_inserts == 0
    # raw (uncompressed) wires take the re-encode path instead
    eng2 = DecodeEngine(cfg, params, max_slots=4, max_seq=64, paged=True,
                        page_size=8)
    for r, w, f in pre.run(_reqs(cfg, [9], 4), compress=False,
                           backend="ref"):
        assert admit_one(eng2, r, f, wire=w, backend="ref")
    assert eng2.reencoded_inserts > 0 and eng2.zero_copy_inserts == 0


def test_padded_wire_inserts_its_cut_rows_with_one_program_a_bucket(
        small_model):
    """A bucketed wire keeps its bucket's rows (``PaddedRows``): the page
    insert scatters them whole, drops the tail past the prompt, and fills
    the pages exactly as the payload cut to the prompt does, with one
    compile for every prompt length of a bucket."""
    from repro.serving.kv_transfer import PaddedRows
    cfg, api, params = small_model
    pre = PrefillEngine(cfg, params, max_seq=64)
    lens = [17, 20, 23, 29]                             # all in bucket 32
    out = pre.run(_reqs(cfg, lens, 4), backend="ref")
    wts = [t for _, w, _ in out for s in w.slots.values() for t in s.values()]
    assert all(isinstance(t.payload, PaddedRows) for t in wts)

    def fill(use_cut):
        eng = DecodeEngine(cfg, params, max_slots=4, max_seq=64, paged=True,
                           page_size=8)
        for r, w, f in out:
            if use_cut:
                w = dataclasses.replace(w, slots={
                    n: {k: dataclasses.replace(t, payload=dict(t.payload))
                        for k, t in s.items()} for n, s in w.slots.items()})
            assert admit_one(eng, r, f, wire=w, backend="ref")
        return eng

    n = []

    def count(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            n.append(duration)
    fill(False)                                 # warm the first length
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        whole = fill(False)
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert n == [], f"{len(n)} compiles for prompt lengths of one bucket"
    cut = fill(True)
    assert whole.zero_copy_inserts == cut.zero_copy_inserts == 2 * len(lens)
    for x, y in zip(jax.tree.leaves(whole.cache),
                    jax.tree.leaves(cut.cache)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_paged_bf16_resident_decodes(small_model):
    cfg, api, params = small_model
    pre = PrefillEngine(cfg, params, max_seq=64)
    eng = DecodeEngine(cfg, params, max_slots=2, max_seq=64, chunk_size=4,
                       paged=True, page_size=8, kv_resident="bf16")
    done = []
    for r, w, f in pre.run(_reqs(cfg, [8, 12], 6), backend="ref"):
        assert admit_one(eng, r, f, wire=w, backend="ref")
    while eng.active:
        done += eng.step()
    assert sorted(len(r.out_tokens) for r in done) == [6, 6]
    assert eng.pool.n_in_use == 0


def test_paged_unsupported_arch_falls_back():
    cfg = get_reduced("xlstm-125m")
    params = build(cfg).init(KEY)
    eng = DecodeEngine(cfg, params, max_slots=2, max_seq=64, paged=True)
    assert not eng.paged and eng.paged_fallback
    pre = PrefillEngine(cfg, params, max_seq=64)
    (r, w, f), = pre.run(_reqs(cfg, [8], 4), backend="ref")
    assert admit_one(eng, r, f, wire=w, backend="ref")
    while eng.active:
        eng.step()
    assert len(r.out_tokens) == 4


def test_paged_pool_survives_phase_flip(small_model):
    """The pool is the DECODE-phase-owned buffer: a drained flip leaves it
    all-free, and a warm re-flip re-enters the same pool (no realloc)."""
    cfg, api, params = small_model
    rep = Replica(cfg, params, phase="decode", max_seq=64,
                  decode_kw={"max_slots": 2, "paged": True, "page_size": 8})
    pre = PrefillEngine(cfg, params, max_seq=64)
    eng = rep.engine
    pool = eng.pool
    for r, w, f in pre.run(_reqs(cfg, [8], 4), backend="ref"):
        assert admit_one(eng, r, f, wire=w, backend="ref")
    with pytest.raises(RuntimeError, match="undrained"):
        rep.switch_phase("prefill")
    while eng.active:
        eng.step()
    rep.switch_phase("prefill")
    rep.switch_phase("decode")
    assert rep.engine is eng and rep.engine.pool is pool
    assert pool.n_in_use == 0 and pool.n_free == pool.capacity


# -- cost model ---------------------------------------------------------------


def test_costmodel_page_budget_credits_compression():
    from repro.configs import get_config
    from repro.core import costmodel as cm
    from repro.core.cluster import make_paper_cloud

    cfg = get_config("llama-30b")
    cluster = make_paper_cloud()
    pc = cm.ParallelConfig(tp=8, pp=1, stages=[list(range(8, 16))],
                           layer_partition=[cfg.num_layers])
    assert cm.paged_kv_supported(cfg)
    paged_cap = cm.max_decode_batch(cluster, cfg, pc, 2048)
    # dense bf16 arithmetic for comparison
    per_seq = 2048 * cm.kv_bytes_per_token(cfg)
    budget = cm.decode_page_budget(cluster, cfg, pc)
    assert budget > 0
    dense_cap = int(budget * cm.PAGE_SIZE
                    * cm.kv_bytes_per_token(cfg, resident="int4")
                    / per_seq)
    assert paged_cap >= 1.5 * max(dense_cap, 1), (paged_cap, dense_cap)
    # int4 residency really is ~7x smaller per token
    ratio = cm.kv_bytes_per_token(cfg) / cm.kv_bytes_per_token(
        cfg, resident="int4")
    assert 3 < ratio < 8
    # recurrent archs keep dense arithmetic
    assert not cm.paged_kv_supported(get_config("jamba-v0.1-52b"))
