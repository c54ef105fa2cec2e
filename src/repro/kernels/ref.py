"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.kv_layout import (interleave_nibbles, pack_nibbles,
                                     split_even_odd)


def flash_attention_ref(q, k, v, *, causal=True, window=0, sm_scale=None):
    """q: (BH, Sq, hd); k/v: (BHkv, Sk, hd) with BH = BHkv * group."""
    BH, Sq, hd = q.shape
    BHkv, Sk, _ = k.shape
    g = BH // BHkv
    sm_scale = sm_scale or 1.0 / math.sqrt(hd)
    qh = q.reshape(BHkv, g, Sq, hd).astype(jnp.float32)
    s = jnp.einsum("bgqd,bkd->bgqk", qh, k.astype(jnp.float32)) * sm_scale
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= kpos <= qpos
    if window:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgqk,bkd->bgqd", w, v.astype(jnp.float32))
    return o.reshape(BH, Sq, hd).astype(q.dtype)


def decode_attention_ref(q, k, v, *, kv_len=None, sm_scale=None):
    """q: (B, Hkv, g, hd); k/v: (B, Hkv, S, hd); kv_len: (B,) valid lengths."""
    B, Hkv, g, hd = q.shape
    S = k.shape[2]
    sm_scale = sm_scale or 1.0 / math.sqrt(hd)
    s = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if kv_len is not None:
        mask = jnp.arange(S)[None, None, None, :] < kv_len[:, None, None, None]
        s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", w, v.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_gather_ref(pages, page_table, *, page_size):
    """Materialize one layer's paged K and V into dense (B, W*page_size,
    Hkv*hd) f32 tensors by gathering each sequence's pages through its
    page table.

    ``pages`` is one layer's pool: int4 ``{"kp", "vp" (P, page_size*ppr,
    g//2) u8, "sz" (P, 4, page_size*ppr) f32}`` (rows: K's scale and zero,
    V's scale and zero) — dequantized here — or bf16 ``{"k", "v" (P,
    page_size, Hkv, hd)}``. The oracle for the fused-dequant Pallas
    kernel."""
    pt = jnp.maximum(page_table, 0)               # 0 = trash page
    if "kp" in pages:
        sz = pages["sz"][pt]                      # (B, W, 4, R)
        out = []
        for packed, col in ((pages["kp"], 0), (pages["vp"], 2)):
            x = kv_dequant_ref(packed[pt], sz[:, :, col, :, None],
                               sz[:, :, col + 1, :, None],
                               dtype=jnp.float32)  # (B, W, R, g)
            B, W, R, g = x.shape
            out.append(x.reshape(B, W * page_size, R // page_size * g))
        return tuple(out)
    out = []
    for a in (pages["k"], pages["v"]):
        gathered = a[pt]                          # (B, W, ps, Hkv, hd)
        B, W, ps, Hkv, hd = gathered.shape
        out.append(gathered.astype(jnp.float32).reshape(B, W * ps,
                                                        Hkv * hd))
    return tuple(out)


def paged_decode_attention_ref(q, pages, page_table, kv_len, layer, *,
                               page_size, sm_scale=None):
    """q: (B, Hkv, gq, hd); ``pages`` the stacked pool the kernel takes,
    read at ``layer``.

    Gathers + dequantizes that layer to dense, then runs the dense decode
    oracle."""
    B, Hkv, gq, hd = q.shape
    pages = {k: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
             for k, a in pages.items()}
    k, v = paged_gather_ref(pages, page_table, page_size=page_size)
    S = k.shape[1]
    k = k.reshape(B, S, Hkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, Hkv, hd).transpose(0, 2, 1, 3)
    return decode_attention_ref(q, k, v, kv_len=kv_len, sm_scale=sm_scale)


def kv_quant_ref(x):
    """Group-wise int4 quantization over the last axis. x: (N, G).

    Returns (packed (N, G//2) uint8, scale (N,1) f32, zero (N,1) f32)."""
    xf = x.astype(jnp.float32)
    mn = xf.min(axis=-1, keepdims=True)
    mx = xf.max(axis=-1, keepdims=True)
    scale = jnp.maximum(mx - mn, 1e-8) / 15.0
    q = jnp.clip(jnp.round((xf - mn) / scale), 0, 15).astype(jnp.uint8)
    packed = pack_nibbles(*split_even_odd(q))
    return packed, scale, mn


def kv_dequant_ref(packed, scale, zero, dtype=jnp.bfloat16):
    q = interleave_nibbles(packed)
    return (q * scale + zero).astype(dtype)


def rmsnorm_ref(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (xf * r * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)
