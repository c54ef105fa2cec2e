"""The plain reference against the program's own forward pass, both block
kinds, at a tiny size on the CPU (the shrink lives in the test only)."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, model
from bench.reference import dense
from bench.tests.conftest import PROGRAM_RMS_EPS, parallel_block, tiny_config

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _config(name):
    """The cell's configuration at a tiny size; ``command-r-32b-l4`` is its
    sizes under Command-R's block kind."""
    cfg = tiny_config(json.loads(
        (CONFIGS / "mistral-7b-l16.json").read_text()))
    return parallel_block(cfg) if name == "command-r-32b-l4" else cfg


@pytest.mark.parametrize("name", ["mistral-7b-l16", "command-r-32b-l4"])
def test_reference_matches_program_forward(name):
    from repro.models import registry, transformer
    cfg = _config(name)
    if "rms_norm_eps" in cfg:
        cfg["rms_norm_eps"] = PROGRAM_RMS_EPS
    mcfg = model.model_config(cfg)
    params = model.make_weights(cfg, 2**35 + 11)
    model.check_tree(cfg, params, registry.build(mcfg).init)
    toks = np.random.default_rng(1).integers(1, 256, 40).astype(np.int32)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x, _, _ = transformer.forward(mcfg.replace(dtype="float32"), p32,
                                      jnp.asarray(toks)[None])
        prog = np.asarray(x[0] @ transformer.unembed_matrix(mcfg, p32))
    served = correct.Served(toks[:20], toks[20:])
    # every position on exact keys and values, as the program's prefill
    hn = correct.final_hidden(cfg, params, served, kv_from=len(toks))
    out_w, tied = correct._out(cfg, params)
    ref = np.asarray(jnp.dot(hn, (out_w.T if tied else out_w).astype(
        jnp.float32), precision="highest"))[:20]
    # same maths in f32 on both sides: only summation order differs
    np.testing.assert_allclose(ref, prog[19:39], rtol=0, atol=1e-4)


def test_interleaved_rotary_equals_half_rotary_under_the_mapping():
    hd = 16
    cfg = {"head_dim": hd, "hidden_size": 64, "num_attention_heads": 4,
           "block": {"rotary": "interleaved"}}
    perm = model._rotary_perm(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 2, hd))
    pos = jnp.arange(5)
    half = dense.rotary(x, pos, 1e4, "half")
    inter = dense.rotary(x[..., perm], pos, 1e4, "interleaved")
    np.testing.assert_allclose(np.asarray(inter), np.asarray(half[..., perm]),
                               atol=1e-6)


def test_int4_store_is_the_programs_kv_encoding():
    """The reference's int4 cache rule, written from the wire format's
    description, reads back what the program's quantizer stores."""
    from repro.kernels import ref
    x = jax.random.normal(jax.random.PRNGKey(3), (37, 2, 64)) * 3.0
    mine = dense.int4_store(x, 128)
    packed, scale, zero = ref.kv_quant_ref(x.reshape(-1, 128))
    theirs = ref.kv_dequant_ref(packed, scale, zero, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(mine).reshape(-1, 128),
                               np.asarray(theirs), rtol=0, atol=1e-5)


def test_decoded_positions_read_the_int4_cache():
    cfg = _config("command-r-32b-l4")
    params = model.make_weights(cfg, 8)
    toks = np.arange(3, 43, dtype=np.int32)
    s = correct.Served(toks[:20], toks[20:])
    exact = np.asarray(correct.final_hidden(cfg, params, s, kv_from=40))
    stored = np.asarray(correct.final_hidden(cfg, params, s))
    # the first served token comes from prefill: exact keys and values
    np.testing.assert_array_equal(exact[0], stored[0])
    assert np.abs(exact[1:20] - stored[1:20]).max() > 1e-4


def test_gap_is_zero_where_the_reference_agrees():
    cfg = _config("mistral-7b-l16")
    params = model.make_weights(cfg, 5)
    prompt = np.arange(1, 30, dtype=np.int32)
    # greedy continuation by the reference itself
    toks = []
    for _ in range(6):
        s = correct.Served(prompt, np.asarray(toks + [0], np.int32))
        hn = correct.final_hidden(cfg, params, s)
        out_w, tied = correct._out(cfg, params)
        _, arg = dense.head_argmax(hn, out_w, tied=tied, n_blocks=1)
        toks.append(int(np.asarray(arg)[len(toks)]))
    g, c = correct.gaps(cfg, params, correct.Served(prompt, np.asarray(
        toks, np.int32)), control=True)
    # the best logit and the served token's logit come from two products
    assert np.all(np.abs(g) < 1e-5)
    assert np.all(c > -1e-5)


def test_fake_quant_is_float8_per_output_channel():
    w = jnp.asarray([[1.0, 100.0], [0.5, -300.0], [0.013, 7.0]])
    q = dense.fake_quant(w, -1, "fp8")
    # one scale per column: each column's largest magnitude maps to 448
    np.testing.assert_allclose(np.asarray(q)[[0, 1], [0, 1]], [1.0, -300.0])
    rel = np.abs(np.asarray(q - w)) / np.abs(np.asarray(w))
    assert rel.max() <= 2.0 ** -4 + 1e-6
    assert np.all(np.asarray(dense.fake_quant(w, -1, "none")) ==
                  np.asarray(w))
