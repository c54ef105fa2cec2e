"""int4 group-wise KV-cache quantization kernels (the paper's wire format).

ThunderServe quantizes KV tensors to 4 bits ONLY for the prefill->decode
transfer, then dequantizes on arrival; compute stays 16-bit (paper §4).
These kernels implement the pack/unpack hot loop: per-group (last axis)
min/max affine quantization, two nibbles packed per uint8.

Tiling: rows are blocked (block_n x G tiles in VMEM); G (the quantization
group, e.g. head_dim) sits in the lane dimension. The quantizer reads the
group as its even and odd halves, split in XLA before the launch
(``kv_layout.split_even_odd``), so the kernel body only packs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# nibble order is THE layout contract shared with the wire and the paged
# cache — it lives in kv_layout (lint rule R005), and the helpers are
# plain jnp expressions so they trace inside the kernel bodies
from repro.kernels.kv_layout import (interleave_nibbles, pack_nibbles,
                                     split_even_odd)


def _quant_kernel(xe_ref, xo_ref, q_ref, scale_ref, zero_ref):
    xe = xe_ref[...].astype(jnp.float32)             # (bn, G//2) even
    xo = xo_ref[...].astype(jnp.float32)             # (bn, G//2) odd
    mn = jnp.minimum(xe.min(axis=-1, keepdims=True),
                     xo.min(axis=-1, keepdims=True))
    mx = jnp.maximum(xe.max(axis=-1, keepdims=True),
                     xo.max(axis=-1, keepdims=True))
    scale = jnp.maximum(mx - mn, 1e-8) / 15.0

    def q(x):
        return jnp.clip(jnp.round((x - mn) / scale), 0, 15).astype(jnp.int32)

    q_ref[...] = pack_nibbles(q(xe), q(xo))
    scale_ref[...] = scale
    zero_ref[...] = mn


def _dequant_kernel(q_ref, scale_ref, zero_ref, x_ref, *, out_dtype):
    q = interleave_nibbles(q_ref[...])               # (bn, Gh*2) f32
    x_ref[...] = (q * scale_ref[...] + zero_ref[...]).astype(out_dtype)


def kv_quant(x, *, block_n=256, interpret=False):
    """x: (N, G) -> (packed (N, G//2) u8, scale (N,1) f32, zero (N,1) f32).

    N need not divide block_n: the grid is ceil-divided and the ragged tail
    block is padded on load / clipped on store by pallas — safe here because
    the min/max reduction is per-row (padding rows never leak into real
    rows). This keeps the batched wire path on one well-tiled launch
    instead of degenerating to tiny blocks for awkward row counts."""
    N, G = x.shape
    assert G % 2 == 0
    block_n = min(block_n, N)
    half = pl.BlockSpec((block_n, G // 2), lambda i: (i, 0))
    return pl.pallas_call(
        _quant_kernel,
        grid=(pl.cdiv(N, block_n),),
        in_specs=[half, half],
        out_specs=[
            pl.BlockSpec((block_n, G // 2), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, G // 2), jnp.uint8),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*split_even_odd(x))


def kv_dequant(packed, scale, zero, *, out_dtype=jnp.bfloat16, block_n=256,
               interpret=False):
    """Inverse of kv_quant. Returns (N, G) in out_dtype. Ragged N is
    handled the same way as in ``kv_quant`` (per-row kernel)."""
    N, Gh = packed.shape
    block_n = min(block_n, N)
    kernel = functools.partial(_dequant_kernel, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(N, block_n),),
        in_specs=[
            pl.BlockSpec((block_n, Gh), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, Gh * 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Gh * 2), out_dtype),
        interpret=interpret,
    )(packed, scale, zero)
