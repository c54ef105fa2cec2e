"""One generator for every traffic mix; the mix is a data file.

``bench/traffic/<mix>.json`` holds:

* ``prompt`` / ``output``: ``{"mean", "cv", "min", "max"}`` of a lognormal
  length law, clipped to [min, max] (the law of ``repro.core.workload``:
  the paper's Azure-trace statistics, section 5.1);
* ``arrival``: ``{"kind": "poisson", "rate": req/s}``;
* ``sizes_seed``: the seed of the set of sizes and gaps;
* ``lead_in_s``: traffic served before the window, not counted;
* ``drain_cap_s``: how long after the window requests due in it may run.

Every run seed gets the same schedule: the sizes and gaps are drawn from
``sizes_seed`` (separately for the lead-in and the window) in a fixed
order, and the run seed draws the prompt token ids. So seeds change the
inputs and what the model serves, not the amount, order or timing of the
work: at the low rates the chip sustains today a window holds a few dozen
requests, and a seed that reordered them would move the tails by more
than a regression worth catching.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np


@dataclass(frozen=True)
class Req:
    rid: int
    due: float          # seconds after the start of the lead-in
    n_in: int
    n_out: int
    tokens: np.ndarray  # prompt token ids, int32


def lognormal(rng: np.random.Generator, mean: float, cv: float,
              size: int) -> np.ndarray:
    """Lognormal samples with the given mean and coefficient of variation
    (as ``repro.core.workload._lognormal``)."""
    sigma2 = math.log(1.0 + cv * cv)
    mu = math.log(mean) - sigma2 / 2.0
    return rng.lognormal(mu, math.sqrt(sigma2), size)


def _lengths(rng, law: dict, n: int) -> np.ndarray:
    x = lognormal(rng, law["mean"], law["cv"], n)
    return np.clip(np.round(x), law["min"], law["max"]).astype(np.int64)


def sizes(mix: dict, n: int, stream: int) -> Tuple[np.ndarray, np.ndarray]:
    """``n`` (prompt, output) length pairs of the mix, fixed by
    ``sizes_seed`` and ``stream`` (one stream per phase)."""
    rng = np.random.default_rng([mix["sizes_seed"], stream])
    return _lengths(rng, mix["prompt"], n), _lengths(rng, mix["output"], n)


def _gaps(mix: dict, duration: float, stream: int) -> np.ndarray:
    """Exponential gaps whose sum stays within ``duration``."""
    rng = np.random.default_rng([mix["sizes_seed"], stream, 1])
    rate = float(mix["arrival"]["rate"])
    out: List[float] = []
    t = 0.0
    while True:
        g = rng.exponential(1.0 / rate)
        if t + g >= duration:
            return np.asarray(out)
        t += g
        out.append(g)


def poisson_schedule(mix: dict, seed: int, window_s: float,
                     vocab: int) -> List[Req]:
    """Requests due over the lead-in and the window, in due order."""
    rng = np.random.default_rng([seed, 7])
    reqs: List[Req] = []
    t0 = 0.0
    for stream, span in ((0, float(mix["lead_in_s"])), (1, float(window_s))):
        gaps = _gaps(mix, span, stream)
        n_in, n_out = sizes(mix, len(gaps), stream)
        due = t0 + np.cumsum(gaps)
        for d, a, b in zip(due, n_in, n_out):
            reqs.append(Req(len(reqs), float(d), int(a), int(b),
                            rng.integers(1, vocab, int(a)).astype(np.int32)))
        t0 += span
    return reqs


def prompt_lengths(mix: dict, window_s: float) -> List[int]:
    """Every distinct prompt length the mix can send in a run of this
    window, whatever the seed (the warm-up compiles each one's shapes)."""
    out = set()
    for stream, span in ((0, float(mix["lead_in_s"])), (1, float(window_s))):
        out |= {int(x) for x in
                sizes(mix, len(_gaps(mix, span, stream)), stream)[0]}
    return sorted(out)
