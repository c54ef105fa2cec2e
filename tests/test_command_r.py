"""Command-R 08-2024 (``bench/configs/command-r-32b-l4.json``) on the
program's normal serving path, at a tiny size on the CPU: the file's
parallel LayerNorm block and tied head, built by ``bench/model.py`` and
``registry.build`` and served by ``deploy.build``'s gateway (prefill, the
int4 wire, paged int4 decode), against the plain float32 reference
(``bench/reference/dense.py``) over the same int4 KV.

The program's logits at each served position come from the same engines
the gateway ran, fed the served tokens: the prefill engine's bucketed
program for the first, the decode engine's paged step for the rest.
"""
import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, deploy, model
from bench.tests.conftest import tiny_config
from repro.models import registry, transformer
from repro.serving.engine import (ADMIT_FRESH, AdmissionBatch, AdmissionItem,
                                  GenRequest)
from repro.serving.gateway import ServeRequest

CONFIG = (Path(__file__).resolve().parents[1] / "bench" / "configs"
          / "command-r-32b-l4.json")
SEED = 2**36 + 5
# (prompt tokens, output tokens) of the served requests
REQUESTS = [(37, 24), (90, 17), (16, 30), (150, 24), (61, 28), (200, 20)]
# Relative RMS of the program's logits from the reference's, over every
# served position of every request, in units of the reference logits'
# spread. The program computes in bf16 with f32 sums and quantizes its own
# bf16 keys and values, so here and there an int4 code lies one step from
# the reference's: 0.021 at this seed. A program that kept its KV in bf16
# would read about 0.05 (the exact-KV reference lies 0.049 from the int4
# one), one with RMSNorm in place of LayerNorm about 0.29
# (``test_tolerance_rejects_bf16_kv_and_rmsnorm``).
TOL = 0.03
# a power of two, so the scaled f32 logits are exact multiples
LOGIT_SCALE = 0.0625


def _rel(got, want) -> float:
    return float(np.sqrt(np.mean((got - want) ** 2)) / want.std())


@pytest.fixture(scope="module")
def published():
    return json.loads(CONFIG.read_text())


@pytest.fixture(scope="module")
def tiny(published):
    cfg = tiny_config(published)
    return cfg, model.make_weights(cfg, SEED)


def _program_logits(dep, params, prompt, toks):
    """Logits at each served position, from the engines ``dep`` built: the
    prefill program at the prompt's bucket, then one paged decode step a
    served token, over the wire's int4 pages."""
    pre, eng = dep.prefill, dep.decode
    req = GenRequest(-1, prompt, len(toks))
    (_, wire, first), = pre.run([req], compress=True)
    n = len(prompt)
    bucket = max(1 << (n - 1).bit_length(), pre.min_bucket)
    batch = {"tokens": np.pad(prompt, (0, bucket - n))[None],
             "last_pos": np.asarray([n - 1], np.int32),
             "true_len": np.asarray([n], np.int32)}
    logits, _ = pre._prefill_fn((1, bucket))(params, batch)
    out = [np.asarray(logits[0, -1])]
    assert not eng.admit(AdmissionBatch([AdmissionItem(req, first,
                                                       ADMIT_FRESH,
                                                       wire=wire)]))
    slot = eng.slots.index(req)
    for t in toks[:-1]:
        cur = np.zeros((eng.max_slots, 1), np.int32)
        cur[slot, 0] = t
        logits, eng.cache = eng._decode(params, eng.cache,
                                        {"tokens": jnp.asarray(cur)})
        out.append(np.asarray(logits[slot, -1]))
    eng.release(slot)
    return np.stack(out), first


def _reference_logits(cfg, params, served, **kw):
    hn = correct.final_hidden(cfg, params, served, **kw)
    out_w, tied = correct._out(cfg, params)
    w = (out_w.T if tied else out_w).astype(jnp.float32)
    return np.asarray(jnp.dot(hn, w, precision="highest"))[
        :len(served.tokens)]


@pytest.fixture(scope="module")
def served(tiny):
    """Each request served through the gateway, with the program's logits
    at its served positions."""
    cfg, params = tiny
    dep = deploy.build(model.model_config(cfg), params, cfg["engine"])
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg["vocab_size"], n).astype(np.int32)
               for n, _ in REQUESTS]
    handles = [dep.gateway.submit(ServeRequest(i, p, max_new_tokens=m))
               for i, (p, (_, m)) in enumerate(zip(prompts, REQUESTS))]
    dep.gateway.run_until_drained()
    assert [h.state for h in handles] == ["DONE"] * len(REQUESTS)
    out = []
    for p, h in zip(prompts, handles):
        toks = np.asarray(h.tokens, np.int32)
        logits, first = _program_logits(dep, params, p, toks)
        assert first == toks[0]
        out.append((correct.Served(p, toks), logits))
    return out


def test_widths_are_the_published_32b(published, tiny):
    whole = dict(published, num_hidden_layers=published["reduced"][
        "num_hidden_layers"])
    assert round(model.model_config(whole).param_count() / 1e9, 1) == 32.3
    # the benchmark's weights have the program's tree, at the file's
    # published widths (shapes only) and at the tiny size
    mcfg = model.model_config(published)
    shapes = jax.eval_shape(lambda k: model.make_params(published, k),
                            jax.random.key(0))
    model.check_tree(published, shapes, registry.build(mcfg).init)
    cfg, params = tiny
    model.check_tree(cfg, params,
                     registry.build(model.model_config(cfg)).init)


def test_served_logits_match_the_reference(tiny, served):
    cfg, params = tiny
    got, want = [], []
    for s, logits in served:
        # the gateway served the program's own greedy tokens
        np.testing.assert_array_equal(logits.argmax(-1), s.tokens)
        got.append(logits)
        want.append(_reference_logits(cfg, params, s))
    assert _rel(np.concatenate(got), np.concatenate(want)) <= TOL


def _rmsnorm_variant(cfg, params):
    """The same weights under an RMSNorm block (the reference maps an
    RMSNorm weight as ``1 + scale``)."""
    c = copy.deepcopy(cfg)
    c["block"] = dict(c["block"], norm="rmsnorm")
    p = dict(params, blocks={"slot0": dict(
        params["blocks"]["slot0"],
        norm1={"scale": params["blocks"]["slot0"]["norm1"]["scale"] - 1})},
        final_norm={"scale": params["final_norm"]["scale"] - 1})
    return c, p


@pytest.mark.parametrize("variant", ["bf16_kv", "rmsnorm"])
def test_tolerance_rejects_bf16_kv_and_rmsnorm(tiny, served, variant):
    """A program that kept exact (bf16) keys and values, or normed with
    RMSNorm, would serve the logits of these references: they lie outside
    the tolerance of the int4 LayerNorm reference, and so does the
    program from them."""
    cfg, params = tiny
    wrong, want, got = [], [], []
    for s, logits in served:
        if variant == "bf16_kv":
            alt = _reference_logits(cfg, params, s,
                                    kv_from=len(s.prompt) + len(s.tokens))
        else:
            alt = _reference_logits(*_rmsnorm_variant(cfg, params), s)
        wrong.append(alt)
        want.append(_reference_logits(cfg, params, s))
        got.append(logits)
    wrong, want = np.concatenate(wrong), np.concatenate(want)
    assert _rel(wrong, want) > TOL
    assert _rel(np.concatenate(got), wrong) > TOL


def test_logit_scale_multiplies_every_served_logit(tiny, served):
    """With Command-R's published ``logit_scale``, the prefill and paged
    decode heads give the scaled reference's logits, and the served tokens
    do not change."""
    cfg, params = tiny
    mcfg = model.model_config(cfg).replace(logit_scale=LOGIT_SCALE)
    dep = deploy.build(mcfg, params, cfg["engine"])
    for s, plain in served:
        scaled, first = _program_logits(dep, params, s.prompt, s.tokens)
        assert first == s.tokens[0]
        np.testing.assert_array_equal(scaled, LOGIT_SCALE * plain)
        ref = LOGIT_SCALE * _reference_logits(cfg, params, s)
        assert _rel(scaled, ref) <= TOL


def test_logit_scale_in_the_loss(tiny):
    cfg, params = tiny
    mcfg = model.model_config(cfg).replace(logit_scale=LOGIT_SCALE)
    toks = np.random.default_rng(3).integers(1, cfg["vocab_size"],
                                             (2, 17)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "targets": jnp.asarray(toks[:, 1:])}
    x, _, _ = transformer.forward(mcfg, params, batch["tokens"])
    logits = transformer.head_logits(mcfg, params, x)
    want = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits),
                                         batch["targets"][..., None], -1))
    got = transformer.loss_fn(mcfg, params, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-3)
