"""THE wire/page quantization-layout contract (single source of truth).

Three subsystems must agree bit-for-bit on how KV tensors are laid out in
their int4 form, or zero-copy page insertion silently degrades to a
re-encode (and a drifted nibble order corrupts tokens outright):

* the prefill->decode wire (``serving/kv_transfer.py``),
* the paged decode cache format (``models/paged.py``),
* the pack/unpack kernels (``kernels/kv_quant.py``, ``kernels/ref.py``,
  ``kernels/paged_attention.py``).

Historically each carried its own copy of the group-candidate tuple and
the nibble pack/unpack expressions — exactly the drift the linter's R005
rule (``repro.analysis``) now forbids. Everything layout-bearing lives
HERE and only here:

* :data:`GROUPS` — candidate quantization group widths;
* :func:`pick_group` — the ONE group-selection rule (largest candidate
  dividing the span; groups never straddle token positions when the span
  is ``Hkv * hd``);
* :func:`split_even_odd` / :func:`pack_nibbles` / :func:`unpack_nibbles`
  — the low-nibble-first two-per-byte int4 packing (element ``2j`` in the
  low nibble of byte ``j``, ``2j+1`` in the high nibble).

This module is dependency-light (jax only) so both ``serving/`` and
``models/`` can import it without cycles, and the helpers are plain jnp
expressions so Pallas kernel bodies can call them while tracing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Candidate quantization group widths (lane dim of the pallas kernel).
# 128-wide groups keep the scale/zero overhead at ~3% even for small
# head_dims; fall back to smaller even groups, then raw (0).
GROUPS = (128, 64, 32, 16, 8, 4, 2)


def pick_group(span: int) -> int:
    """Largest candidate group width dividing ``span`` (0 = none: raw).

    Used by the wire's padded-extract path with ``span = Hkv * hd`` (so
    groups are position-aligned) and by the page format with the same
    span — both MUST go through this function (lint rule R005)."""
    return next((g for g in GROUPS if span % g == 0), 0)


def split_even_odd(x):
    """``(..., G)`` -> the ``(..., G//2)`` planes that :func:`pack_nibbles`
    puts in the low (even elements) and high (odd elements) nibbles.

    A lane-strided split, so it runs in XLA ahead of the quantization
    kernel: Mosaic refuses the ``(n, G) -> (n, G//2, 2)`` reshape."""
    return x[..., 0::2], x[..., 1::2]


def pack_nibbles(even, odd):
    """Pack two int4 planes into one uint8 plane, LOW NIBBLE FIRST:
    ``even`` lands in bits 0-3, ``odd`` in bits 4-7. The arithmetic runs
    in int32: Mosaic has no float<->uint8 casts, only int32<->uint8."""
    even = even.astype(jnp.int32)
    odd = odd.astype(jnp.int32)
    return (even | (odd << 4)).astype(jnp.uint8)


def unpack_nibbles(packed):
    """Inverse of :func:`pack_nibbles`: uint8 plane -> (low, high) f32
    planes. Callers interleave with :func:`interleave_nibbles` (or keep
    the planes separate when the layout wants them that way)."""
    w = packed.astype(jnp.int32)
    lo = (w & 0xF).astype(jnp.float32)
    hi = (w >> 4).astype(jnp.float32)
    return lo, hi


def interleave_nibbles(packed):
    """uint8 ``(..., G//2)`` -> f32 ``(..., G)`` restoring the original
    element order (even indices from low nibbles, odd from high)."""
    lo, hi = unpack_nibbles(packed)
    return jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (packed.shape[-1] * 2,))


def interleave_nibbles_mxu_t(packed_t):
    """:func:`interleave_nibbles` down the sublanes, as two matmuls: a
    ``(G//2, n)`` plane whose columns are the packed rows -> ``(G, n)``
    f32 whose column ``c`` is row ``c`` interleaved, for Pallas TPU
    kernel bodies that read packed rows lane-dense (Mosaic builds a
    stack-and-reshape interleave out of 2-lane tiles, many times the
    block's VMEM). Exact: the nibbles (0..15) and the 0/1 placement
    matrices are exact in bf16, and each output element sums one product
    in f32."""
    lo, hi = unpack_nibbles(packed_t)
    half = packed_t.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (2 * half, half), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (2 * half, half), 1)

    def place(plane, odd):
        return jnp.dot((row == 2 * col + odd).astype(jnp.bfloat16),
                       plane.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return place(lo, 0) + place(hi, 1)
