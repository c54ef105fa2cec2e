import json
import math
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"
# repro.core.workload's CONVERSATION: (mean_in, cv_in, mean_out, cv_out)
PAPER = {"conversation": (1024, 0.6, 129, 0.9)}


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(PAPER))
def test_mix_states_the_paper_parameters(name):
    mix = _mix(name)
    mi, ci, mo, co = PAPER[name]
    assert (mix["prompt"]["mean"], mix["prompt"]["cv"]) == (mi, ci)
    assert (mix["output"]["mean"], mix["output"]["cv"]) == (mo, co)


@pytest.mark.parametrize("mean,cv", [(1024, 0.6), (129, 0.9), (16, 0.8)])
def test_lognormal_sample_moments(mean, cv):
    x = traffic.lognormal(np.random.default_rng(0), mean, cv, 400_000)
    assert abs(x.mean() / mean - 1) < 0.02
    assert abs(x.std() / x.mean() / cv - 1) < 0.05


def test_schedule_is_deterministic_per_seed():
    mix = _mix("conversation")
    a = traffic.poisson_schedule(mix, 2**40 + 3, 30.0, 32000)
    b = traffic.poisson_schedule(mix, 2**40 + 3, 30.0, 32000)
    assert [(r.due, r.n_in, r.n_out) for r in a] == \
        [(r.due, r.n_in, r.n_out) for r in b]
    assert all((x.tokens == y.tokens).all() for x, y in zip(a, b))


def test_seeds_change_the_tokens_not_the_work():
    mix = _mix("conversation")
    a = traffic.poisson_schedule(mix, 1, 30.0, 32000)
    b = traffic.poisson_schedule(mix, 2**41 + 9, 30.0, 32000)
    assert [(r.due, r.n_in, r.n_out) for r in a] == \
        [(r.due, r.n_in, r.n_out) for r in b]
    assert not any((x.tokens == y.tokens).all() for x, y in zip(a, b))
    assert max(r.due for r in a) < mix["lead_in_s"] + 30.0


def test_lengths_respect_bounds_and_rate():
    mix = _mix("conversation")
    reqs = traffic.poisson_schedule(mix, 5, 200.0, 32000)
    assert all(64 <= r.n_in <= 4095 and 8 <= r.n_out <= 1024 for r in reqs)
    assert all(len(r.tokens) == r.n_in and r.tokens.min() >= 1
               for r in reqs)
    rate = mix["arrival"]["rate"]
    n = len(reqs)
    want = rate * (200.0 + mix["lead_in_s"])
    assert abs(n - want) < 4 * math.sqrt(want)


def test_prompt_lengths_cover_every_seed():
    mix = _mix("conversation")
    need = set(traffic.prompt_lengths(mix, 30.0))
    for seed in (0, 1, 2**33):
        got = {r.n_in for r in traffic.poisson_schedule(mix, seed, 30.0, 100)}
        assert got <= need

