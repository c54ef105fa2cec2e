"""Plain float32 forward pass of a dense decoder, from the published
descriptions, at ``precision="highest"``. It imports nothing of the
program.

Two block kinds:

* ``sequential`` (Mistral-7B, ``MistralForCausalLM``): RMSNorm
  ``x * rsqrt(mean(x^2) + eps) * w``; ``h = x + attn(norm1(x))``;
  ``out = h + mlp(norm2(h))``; rotary on pairs (i, i + hd/2).
* ``parallel`` (Command-R, ``CohereForCausalLM``): LayerNorm without bias,
  ``(x - mean) * rsqrt(var + eps) * w``; ``out = x + attn(n(x)) + mlp(n(x))``
  with one shared norm; rotary on interleaved pairs (2i, 2i+1); tied
  embedding. Departure: the published ``logit_scale`` (0.0625) multiplies
  the logits there; it is left out here, as the program leaves it out.

Attention is causal, grouped-query (query head h reads kv head
``h // (H / Hkv)``), softmax over ``q.k / sqrt(hd)``; the MLP is
``down(silu(gate(x)) * up(x))``.

The KV cache of the deployment (ThunderServe's int4 wire and pages, as the
configuration's ``engine`` states them): each token's rotated keys and
its values, flattened over heads, are cut into groups of ``kv_group``
values and stored as 4-bit codes with an affine scale and zero per group,
``scale = (max - min) / 15``, ``code = round((x - min) / scale)`` clipped to
[0, 15], read back as ``code * scale + min``. A prompt's own positions
attend over exact keys and values (prefill); every position from
``kv_from`` on (the decoded tokens) attends over the stored ones.

Weights come in the published layout (``wq (d, H*hd)``, ``gate``, ``up``,
``down``, norm weights); matrices may be bf16 and are used at their
values in f32. Sequences run one layer at a time and attention in blocks
of queries, so a long sequence fits beside the served weights. With
``quant="fp8"`` every matrix is rounded to float8 e4m3 with one scale per
output channel before use: the precision a cheaper program would drop to,
which the benchmark's control runs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512           # query rows per attention block
F8_MAX = 448.0          # largest float8 e4m3fn value
F32 = jnp.float32


def fake_quant(w, axis: int, quant: str):
    """``w`` in f32, rounded to float8 e4m3 with one scale per slice along
    ``axis`` (the output channel) when ``quant == "fp8"``."""
    w = w.astype(F32)
    if quant == "none":
        return w
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")
    red = tuple(i for i in range(w.ndim) if i != axis % w.ndim)
    s = jnp.max(jnp.abs(w), axis=red, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _dot(x, w, quant):
    return jnp.dot(x, fake_quant(w, -1, quant), precision=HIGHEST)


def norm(x, w, kind: str, eps: float):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * w.astype(F32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rotary(x, pos, theta: float, style: str):
    """x: (T, n, hd); pos: (T,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    if style == "half":
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def int4_store(x, group: int):
    """Keys or values ``(T, Hkv, hd)`` as the int4 cache holds them."""
    g = x.reshape(x.shape[0], -1, group)
    mn = jnp.min(g, -1, keepdims=True)
    scale = jnp.maximum(jnp.max(g, -1, keepdims=True) - mn, 1e-8) / 15.0
    code = jnp.clip(jnp.round((g - mn) / scale), 0, 15)
    return (code * scale + mn).reshape(x.shape)


def attention(q, k, v, kq, vq, kv_from):
    """Causal grouped-query attention. q (T, H, hd); k, v (T, Hkv, hd)
    exact and ``kq``, ``vq`` as stored; rows at ``kv_from`` and after read
    the stored ones. T a multiple of ``Q_BLOCK``. Returns (T, H*hd)."""
    T, H, hd = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    nb = T // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, Hkv, g, hd)
    kpos = jnp.arange(T)

    def attend(qi, qpos, kk, vv):
        s = jnp.einsum("qhgd,khd->hgqk", qi, kk,
                       precision=HIGHEST) / math.sqrt(hd)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, vv, precision=HIGHEST)

    def block(args):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        stored = (qpos >= kv_from)[:, None, None, None]
        return jnp.where(stored, attend(qi, qpos, kq, vq),
                         attend(qi, qpos, k, v))

    o = jax.lax.map(block, (jnp.arange(nb), qb))
    return o.reshape(T, H * hd)


@functools.partial(jax.jit, static_argnames=(
    "kind", "parallel", "rotary_style", "eps", "theta", "H", "Hkv",
    "kv_group", "quant"))
def layer(h, w, pos, kv_from, *, kind, parallel, rotary_style, eps, theta,
          H, Hkv, kv_group, quant="none"):
    """One decoder layer on f32 hidden states ``h (T, d)``; positions from
    ``kv_from`` on attend over the int4-stored cache."""
    T = h.shape[0]
    x = norm(h, w["ln1"], kind, eps)
    hd = w["wq"].shape[1] // H
    q = _dot(x, w["wq"], quant).reshape(T, H, hd)
    k = _dot(x, w["wk"], quant).reshape(T, Hkv, hd)
    v = _dot(x, w["wv"], quant).reshape(T, Hkv, hd)
    q = rotary(q, pos, theta, rotary_style)
    k = rotary(k, pos, theta, rotary_style)
    a = _dot(attention(q, k, v, int4_store(k, kv_group),
                       int4_store(v, kv_group), kv_from), w["wo"], quant)

    def mlp(y):
        return _dot(jax.nn.silu(_dot(y, w["gate"], quant))
                    * _dot(y, w["up"], quant), w["down"], quant)

    if parallel:
        return h + a + mlp(x)
    h = h + a
    return h + mlp(norm(h, w["ln2"], kind, eps))


@functools.partial(jax.jit, static_argnames=("quant",))
def embed(table, ids, quant="none"):
    """Rows of the embedding (one scale per row under fp8)."""
    return fake_quant(table[ids], 0, quant)


def _out_blocks(out_w, tied: bool, n_blocks: int):
    """The output matrix as ``(n_blocks, V/n_blocks, d)`` rows."""
    if tied:
        V, d = out_w.shape
        return out_w.reshape(n_blocks, V // n_blocks, d)
    d, V = out_w.shape
    return jnp.moveaxis(out_w.reshape(d, n_blocks, V // n_blocks), 0, -1)


@functools.partial(jax.jit, static_argnames=("tied", "n_blocks", "quant"))
def head_argmax(hn, out_w, *, tied, n_blocks, quant="none"):
    """Largest logit and its token at each row of normed ``hn (n, d)``,
    over vocabulary blocks (a row of the output matrix is one token)."""
    blocks = _out_blocks(out_w, tied, n_blocks)
    rows = blocks.shape[1]

    def step(carry, args):
        best, arg = carry
        i, wb = args
        lg = jnp.dot(hn, fake_quant(wb, 0, quant).T, precision=HIGHEST)
        m = jnp.max(lg, -1)
        a = jnp.argmax(lg, -1) + i * rows
        take = m > best
        return (jnp.where(take, m, best), jnp.where(take, a, arg)), None

    init = (jnp.full(hn.shape[:1], -jnp.inf, F32),
            jnp.zeros(hn.shape[:1], jnp.int32))
    (best, arg), _ = jax.lax.scan(step, init,
                                  (jnp.arange(n_blocks), blocks))
    return best, arg


@functools.partial(jax.jit, static_argnames=("tied",))
def head_logits_at(hn, out_w, ids, *, tied):
    """Logit of token ``ids[i]`` at row ``i`` (full precision)."""
    rows = out_w[ids] if tied else out_w[:, ids].T
    return jnp.einsum("nd,nd->n", hn, rows.astype(F32), precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("kind", "eps"))
def final_norm(h, w, *, kind, eps):
    return norm(h, w, kind, eps)
