"""A whole run at a tiny size on the CPU, past the harness's look for a
chip, with the timed path broken underneath: ``correct`` must come out
false for every fault a served cell can have, and true without one.

Faults: a token altered where the decode engine produces it; the KV
exchange between prefill and decode left out (the wire arrives empty);
a decode step that returns its cache unchanged (the new tokens' keys and
values are never written). The control goes through the same check: the
served tokens replaced by the ones the float8 reference puts first. The half-batch and cross-chip faults of the
benchmark's rules belong to training and to multi-chip cells; no cell here
has them.
"""
import time

import jax
import numpy as np
import pytest

from bench import correct, flops, model, spec
from bench import run as brun
from bench.tests.conftest import tiny_config, tiny_mix

# the tiny model's own limit: the sound run of this seed reads 0.25 (bf16,
# and RMSNorm eps 1e-6 against the reference's published 1e-5, with the
# same int4 cache); the faults and the float8 control read 3.0 to 4.9
TINY_LIMIT = 1.0
SEED = 2**33 + 1


def _token_altered(dep):
    eng, orig = dep.decode, dep.decode.step
    V = dep.decode.cfg.vocab_size

    def step(n_steps=None):
        live = [r for r in eng.slots if r is not None]
        fin = orig(n_steps)
        for r in live:
            if len(r.out_tokens) > 2:
                r.out_tokens[-1] = (r.out_tokens[-1] + 1) % V
        return fin
    eng.step = step


def _exchange_dropped(dep):
    tr = dep.gateway.transport
    orig = tr.send

    def send(wire, src, dst, *, now=None):
        import jax.numpy as jnp
        for slot in wire.slots.values():
            for wt in slot.values():
                wt.payload = {k: jnp.zeros_like(v)
                              for k, v in wt.payload.items()}
        return orig(wire, src, dst, now=now)
    tr.send = send


def _state_unchanged(dep):
    eng, orig = dep.decode, dep.decode.step

    def step(n_steps=None):
        pages = {k: v for k, v in eng.cache.items() if k.startswith("slot")}
        fin = orig(n_steps)
        eng.cache.update(pages)
        return fin
    eng.step = step


@pytest.fixture(scope="module")
def compiles():
    counter = brun.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    return counter


def _control_tokens(cfg):
    """A ``served`` hook: each served token replaced by the float8
    reference's first token at its position (the weights are the run's,
    made again from the seed)."""
    params = model.make_weights(cfg, SEED)

    def swap(done):
        return [correct.Served(s.prompt, np.asarray(correct.control_argmax(
            cfg, params, s))[:len(s.tokens)].astype(np.int32))
            for s in done]
    return swap


def _run(workload, fault, compiles):
    cell = spec.resolve(workload)
    cell.config = tiny_config(cell.config)
    cell.traffic = tiny_mix(cell.traffic)
    cell.limits = {"max_logit_gap": {"limit": TINY_LIMIT}}
    if fault is _control_tokens:
        hooks = {"served": fault(cell.config)}
    else:
        hooks = {"deployment": fault} if fault else {}
    return brun.run_cell(cell, SEED, 2.0, False,
                         t_process=time.perf_counter(),
                         device=jax.devices()[0],
                         peaks=flops.peaks("TPU v5 lite"),
                         compiles=compiles, hooks=hooks)


@pytest.mark.parametrize("fault", [None, _token_altered, _exchange_dropped,
                                   _state_unchanged, _control_tokens],
                         ids=["sound", "token_altered", "exchange_dropped",
                              "state_unchanged", "control_fp8"])
def test_fault_under_the_timed_path_is_not_correct(fault, compiles):
    out = _run("mistral7b-conversation", fault, compiles)
    gap = out["checks"]["max_logit_gap"]["value"]
    assert out["window"]["finished"] > 0
    assert list(out)[-1] == "checks"
    if fault is None:
        assert out["correct"], gap
        # the warm-up compiled every shape the window ran
        assert out["window"]["compiles_in_window"] == 0
    else:
        assert not out["correct"], gap
