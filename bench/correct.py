"""Whether the timed path served the right tokens.

Once the window has closed, a sample drawn from the seed of the requests
the run finished, the longest among them, is run through the plain
reference (``bench/reference/dense.py``) over each prompt and its served
tokens. At every served position the reference's best logit minus its
logit for the served token is a gap; the number compared is the widest
gap in the sample. A served token the reference also puts first has gap 0.

The control (not part of a benchmark run): the reference with its
matrices in float8 puts its own first token at each of the same positions,
and that token's gap under the full-precision reference is read the same
way.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from bench import model

TARGET_TOKENS = 300     # served tokens in the sample, at least
MAX_REQUESTS = 16
VOCAB_BLOCK = 16384     # output rows per block of the reference's head


@dataclass
class Served:
    prompt: np.ndarray
    tokens: np.ndarray


def choose(done: Sequence[Served], rng: np.random.Generator) -> List[Served]:
    """The longest finished request, then others in seeded order until the
    sample holds ``TARGET_TOKENS`` served tokens."""
    if not done:
        return []
    order = sorted(range(len(done)), key=lambda i: -(len(done[i].prompt)
                                                     + len(done[i].tokens)))
    pick = [order[0]]
    rest = [order[i] for i in rng.permutation(np.arange(1, len(order)))]
    n = len(done[order[0]].tokens)
    for i in rest:
        if n >= TARGET_TOKENS or len(pick) >= MAX_REQUESTS:
            break
        pick.append(i)
        n += len(done[i].tokens)
    return [done[i] for i in pick]


def _bucket_t(n: int) -> int:
    return 512 if n <= 512 else -(-n // 1024) * 1024


def _bucket_m(n: int) -> int:
    return -(-n // 256) * 256


def _n_blocks(V: int) -> int:
    return next(b for b in range(1, V + 1) if V % b == 0
                and V // b <= VOCAB_BLOCK)


def _eps(cfg: dict) -> float:
    return float(cfg.get("rms_norm_eps") or cfg["layer_norm_eps"])


def final_hidden(cfg: dict, params, s: Served, quant: str = "none",
                 kv_from: Optional[int] = None):
    """Normed final hidden states at the positions that predict each
    served token: ``(Mb, d)`` rows, the first ``len(s.tokens)`` real.
    Positions from ``kv_from`` (default: the first decoded one) attend
    over the int4-stored cache."""
    import jax.numpy as jnp

    from bench.reference import dense
    k = model.dims(cfg)
    blk = cfg["block"]
    seq = np.concatenate([s.prompt, s.tokens[:-1]]).astype(np.int32)
    T, n_in, m = len(seq), len(s.prompt), len(s.tokens)
    ids = np.zeros(_bucket_t(T), np.int32)
    ids[:T] = seq
    outer = model.published_outer(cfg, params)
    h = dense.embed(outer["embed"], jnp.asarray(ids), quant=quant)
    pos = jnp.arange(len(ids))
    for layer in range(k["L"]):
        w = model.published_layer(cfg, params, layer)
        h = dense.layer(h, w, pos, n_in if kv_from is None else kv_from,
                        kind=blk["norm"],
                        parallel=bool(blk["parallel_residual"]),
                        rotary_style=blk["rotary"], eps=_eps(cfg),
                        theta=float(cfg["rope_theta"]), H=k["H"],
                        Hkv=k["Hkv"], kv_group=int(cfg["engine"]["kv_group"]),
                        quant=quant)
        del w
    idx = np.full(_bucket_m(m), n_in - 1, np.int32)
    idx[:m] = np.arange(n_in - 1, n_in - 1 + m)
    return dense.final_norm(h[jnp.asarray(idx)], outer["ln_f"],
                            kind=blk["norm"], eps=_eps(cfg))


def _out(cfg: dict, params):
    outer = model.published_outer(cfg, params)
    tied = outer["unembed"] is None
    return (outer["embed"] if tied else outer["unembed"]), tied


def gaps(cfg: dict, params, s: Served, control: bool = False):
    """Per served position: the reference's gap for the served token and,
    with ``control``, for the float8 reference's first token."""
    import jax.numpy as jnp

    from bench.reference import dense
    out_w, tied = _out(cfg, params)
    nb = _n_blocks(model.dims(cfg)["V"])
    m = len(s.tokens)
    hn = final_hidden(cfg, params, s)
    best, _ = dense.head_argmax(hn, out_w, tied=tied, n_blocks=nb)
    ids = np.zeros(hn.shape[0], np.int32)
    ids[:m] = s.tokens
    got = dense.head_logits_at(hn, out_w, jnp.asarray(ids), tied=tied)
    served = np.asarray(best - got)[:m]
    if not control:
        return served, None
    arg = control_argmax(cfg, params, s)
    got_c = dense.head_logits_at(hn, out_w, arg, tied=tied)
    return served, np.asarray(best - got_c)[:m]


def control_argmax(cfg: dict, params, s: Served):
    """The token the float8 reference puts first at each served position
    (rows padded as :func:`final_hidden`'s; the first ``len(s.tokens)``
    are real)."""
    from bench.reference import dense
    out_w, tied = _out(cfg, params)
    hc = final_hidden(cfg, params, s, quant="fp8")
    _, arg = dense.head_argmax(hc, out_w, tied=tied,
                               n_blocks=_n_blocks(model.dims(cfg)["V"]),
                               quant="fp8")
    return arg


def widest_gap(cfg: dict, params, sample: Sequence[Served]) -> float:
    w = 0.0
    for s in sample:
        g, _ = gaps(cfg, params, s)
        w = max(w, float(np.max(g)))
    return w if sample else math.nan
