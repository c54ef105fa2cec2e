"""Operations and bytes the algorithm needs, computed from shapes, and the
chip's peaks by ``device_kind``.

FLOPs count multiply-adds as two and only the work the served tokens
need: unpadded prompt tokens, live decode slots at their own context
lengths. Bytes count what a kernel must move at least once.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Sequence

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE} (have {sorted(table)})")
    return table[device_kind]


def layer_matmul_params(k: Dict[str, int]) -> int:
    """Weights a token meets in one layer's matrix products."""
    d, H, Hkv, hd, f = k["d"], k["H"], k["Hkv"], k["hd"], k["f"]
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * f


def prefill_flops(k: Dict[str, int], n: int) -> float:
    """One prompt of ``n`` tokens: every layer's matrix products, causal
    attention (query i meets keys 0..i, QK and PV each 2*hd per key and
    head), and the output projection at the last position only."""
    per_layer = (2 * layer_matmul_params(k) * n
                 + 2 * k["H"] * k["hd"] * n * (n + 1))
    return k["L"] * per_layer + 2 * k["d"] * k["V"]


def decode_token_flops(k: Dict[str, int], keys: int) -> float:
    """One decode token attending over ``keys`` positions (its own
    included), with the full output projection."""
    return (k["L"] * (2 * layer_matmul_params(k) + 4 * k["H"] * k["hd"] * keys)
            + 2 * k["d"] * k["V"])


def chunk_keys(ctx: int, emitted: int) -> Iterable[int]:
    """Keys attended at each step of a chunk for a slot holding ``ctx``
    positions before it that emitted ``emitted`` tokens in it."""
    return range(ctx + 1, ctx + emitted + 1)


def kv_quant_bytes(rows: int, group: int) -> float:
    """The int4 quantizer over ``rows`` groups of ``group`` bf16 values:
    reads the values, writes packed nibbles and an f32 scale and zero."""
    return rows * (2 * group + group // 2 + 8)


def wire_rows(k: Dict[str, int], bucket: int, group: int) -> int:
    """Rows the prefill wire quantizes for one prompt padded to
    ``bucket``: K and V of every layer, ``Hkv*hd/group`` rows a token."""
    return 2 * k["L"] * bucket * (k["Hkv"] * k["hd"] // group)


def paged_attn_bytes(k: Dict[str, int], keys: Sequence[int], group: int,
                     act_bytes: int = 2) -> float:
    """One layer's paged int4 attention for live slots attending over
    ``keys`` positions each: packed K and V nibbles with their f32 scale and
    zero per group, plus each slot's query and output."""
    ppr = k["Hkv"] * k["hd"] // group
    kv = sum(keys) * ppr * 2 * (group // 2 + 8)
    qo = len(keys) * 2 * k["H"] * k["hd"] * act_bytes
    return kv + qo


def paged_attn_flops(k: Dict[str, int], keys: Sequence[int]) -> float:
    """One layer's attention products for live slots (QK and PV)."""
    return 4 * k["H"] * k["hd"] * sum(keys)


def roofline_s(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
