"""KV-cache wire format for prefill -> decode transfer (paper §4).

One-shot int4 group-wise quantization on the sender, immediate dequantization
on the receiver; both phases compute in 16-bit. For SSM/hybrid archs the
"KV" is the recurrent-state snapshot (beyond-paper generalization, see
DESIGN.md §Arch-applicability): bounded-size tensors transferred the same
way (f32 states are sent raw — they are O(1)-sized).

Fast path (DESIGN.md §4): all of a request's layers are stacked into ONE
``ops.kv_quant`` pallas call (``extract``), and an entire prefill batch can
be quantized in a single call (``extract_batch``). Payloads stay device
arrays end to end; ``KVWire.materialize()`` is the single explicit
device->host synchronization point for deployments where the wire is a real
network hop. In-process, the decode side consumes device arrays directly and
no host round-trip ever happens.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.serving import tracing
# the ONE layout contract (candidate groups + selection rule) — local
# copies of these constants are forbidden (lint rule R005)
from repro.kernels.kv_layout import GROUPS as _GROUPS
from repro.kernels.kv_layout import pick_group as _pick_group

# Row-tile size for the quant kernels; the kernel handles ragged tails
# (ceil-div grid), so one fixed block => one jit variant per flat shape.
_BLOCK_N = 256


@dataclass
class WireTensor:
    """Either a quantized (packed, scale, zero, orig_shape) or raw tensor.

    Payload arrays may be jax device arrays (fast path) or numpy arrays
    (after ``KVWire.materialize()`` — the explicit wire hop).
    """
    kind: str                      # "int4" | "raw"
    payload: Dict[str, np.ndarray]
    orig_shape: Tuple[int, ...] = ()
    dtype: str = "bfloat16"

    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.payload.values()))


@dataclass
class KVWire:
    """Per-request transferable cache state."""
    request_len: int
    slots: Dict[str, Dict[str, WireTensor]]

    def nbytes(self) -> int:
        return sum(t.nbytes() for s in self.slots.values()
                   for t in s.values())

    def materialize(self) -> "KVWire":
        """Pull every payload to the host in ONE device synchronization.

        This is the single point where the wire leaves the device — call it
        when the transfer crosses a real network boundary; skip it for
        in-process handoff (the decode side consumes device arrays)."""
        tensors = [t for s in self.slots.values() for t in s.values()]
        host = jax.device_get([t.payload for t in tensors])
        for t, p in zip(tensors, host):
            t.payload = p
        return self


def _quantize_stacked(xs: Sequence[jnp.ndarray], backend: str,
                      group: Optional[int] = None) -> List[WireTensor]:
    """Quantize same-shaped tensors in ONE kernel launch.

    Group boundaries never cross a tensor (each tensor's element count is a
    multiple of the group width), so the result is bit-identical to
    quantizing each tensor separately. ``group`` overrides the group width
    (the padded-extract path needs position-aligned groups)."""
    shape = tuple(xs[0].shape)
    n = int(np.prod(shape))
    g = group if group else _pick_group(n)
    if n == 0 or g == 0:
        return [WireTensor("raw", {"x": x}, shape, str(x.dtype)) for x in xs]
    flat = jnp.concatenate([x.reshape(-1, g) for x in xs], axis=0)
    packed, scale, zero = ops.kv_quant(flat, backend=backend,
                                       block_n=_BLOCK_N)
    rows_per = n // g
    out = []
    for t, x in enumerate(xs):
        sl = slice(t * rows_per, (t + 1) * rows_per)
        out.append(WireTensor("int4", {"packed": packed[sl],
                                       "scale": scale[sl],
                                       "zero": zero[sl]},
                              shape, str(x.dtype)))
    return out


def _quantize(x: jnp.ndarray, backend: str) -> WireTensor:
    return _quantize_stacked([x], backend)[0]


def _dequantize(w: WireTensor, backend: str) -> jnp.ndarray:
    if w.kind == "raw":
        return jnp.asarray(w.payload["x"])
    out = ops.kv_dequant(jnp.asarray(w.payload["packed"]),
                         jnp.asarray(w.payload["scale"]),
                         jnp.asarray(w.payload["zero"]),
                         backend=backend)
    return out.reshape(w.orig_shape)


def _extract_one(cache, batch_index: int, length: int, compress: bool,
                 pad_to: Optional[int] = None
                 ) -> Tuple[Dict[str, Dict[str, WireTensor]],
                            List[Tuple[str, str, jnp.ndarray, int]]]:
    """Slice one request out of the cache; return (slots, pending-quant
    jobs). Jobs are quantized by the caller so they can be batched across
    requests. When ``pad_to`` is set, attention KV is sliced to the padded
    length (uniform shapes across a prefill bucket -> one kernel launch for
    the whole batch); the true length rides along for post-quant trimming."""
    slots: Dict[str, Dict[str, WireTensor]] = {}
    jobs: List[Tuple[str, str, jnp.ndarray, int]] = []
    for name, slot in cache.items():
        if name == "lengths":
            continue
        out: Dict[str, WireTensor] = {}
        if isinstance(slot, dict) and "k" in slot:        # attention KV
            s_cache = slot["k"].shape[2]
            ln = min(length, s_cache)
            lp = min(pad_to, s_cache) if pad_to else ln
            lp = max(lp, ln)
            for key in ("k", "v"):
                if compress:
                    t = slot[key][:, batch_index, :lp]     # (L, lp, Hkv, hd)
                    jobs.append((name, key, t, ln))
                else:
                    t = slot[key][:, batch_index, :ln]     # (L, len, Hkv, hd)
                    out[key] = WireTensor("raw", {"x": t}, tuple(t.shape),
                                          str(t.dtype))
        elif isinstance(slot, dict):                       # recurrent states
            for key, arr in slot.items():
                st = arr[:, batch_index]
                out[key] = WireTensor("raw", {"x": st},
                                      tuple(st.shape), str(st.dtype))
        elif getattr(slot, "ndim", 0) >= 4:  # flat decoder KV (whisper):
            # self_* arrays are per-token KV (trim to the request);
            # cross_* arrays span the encoder sequence (transfer whole)
            per_token = name.startswith("self_")
            s_cache = slot.shape[2]
            ln = min(length, s_cache) if per_token else s_cache
            lp = min(pad_to, s_cache) if (pad_to and per_token) else ln
            lp = max(lp, ln)
            if compress:
                jobs.append((name, "x", slot[:, batch_index, :lp], ln))
            else:
                t = slot[:, batch_index, :ln]
                out["x"] = WireTensor("raw", {"x": t}, tuple(t.shape),
                                      str(t.dtype))
        slots[name] = out
    return slots, jobs


def extract(cache, batch_index: int, length: int, *, compress: bool = True,
            backend: str = "auto") -> KVWire:
    """Pull one request's state out of a prefill cache pytree.

    All attention layers are quantized in one kernel launch per distinct
    tensor shape (usually exactly one); nothing is synced to the host."""
    return extract_batch(cache, [(batch_index, length)], compress=compress,
                         backend=backend)[0]


def _trim_wire_tensor(wt: WireTensor, ln: int) -> WireTensor:
    """Cut a padded-length int4 WireTensor down to the request's true
    length (lazy device slicing — no dequantization, no kernel launch).

    Requires the quantization groups to be position-aligned (group width
    divides Hkv*hd), which ``extract_batch`` checks before taking the
    padded path."""
    if wt.kind != "int4":
        return wt
    L, lp, Hkv, hd = wt.orig_shape
    if ln >= lp:
        return wt
    packed = wt.payload["packed"]
    g2 = packed.shape[1]                      # group//2 packed bytes
    ppr = (Hkv * hd) // (2 * g2)              # quant rows per position
    out = {}
    for key, a in wt.payload.items():
        out[key] = a.reshape(L, lp, ppr, a.shape[1])[:, :ln].reshape(
            -1, a.shape[1])
    return WireTensor("int4", out, (L, ln, Hkv, hd), wt.dtype)


def extract_batch(cache, requests: Sequence[Tuple[int, int]], *,
                  compress: bool = True, backend: str = "auto",
                  pad_to: Optional[int] = None) -> List[KVWire]:
    """Extract several (batch_index, length) requests, batching the
    quantization across requests AND layers: one ``ops.kv_quant`` call per
    distinct sliced-tensor shape. With ``pad_to`` (bucketed prefill), every
    request is sliced to the same padded length so the WHOLE batch is one
    kernel launch; the packed rows are then trimmed to each request's true
    length without leaving the device."""
    with tracing.span("wire", lambda: {"requests": len(requests),
                                       "pad_to": pad_to or 0}):
        return _extract_batch(cache, requests, compress, backend, pad_to)


def _extract_batch(cache, requests, compress, backend, pad_to):
    group = None
    if pad_to is not None:
        # padded-path precondition: groups must not straddle positions, so
        # each request's true-length rows can be sliced out post-quant
        for name, slot in cache.items():
            if name == "lengths":
                continue
            if isinstance(slot, dict) and "k" in slot:
                span = int(np.prod(slot["k"].shape[-2:]))
            elif not isinstance(slot, dict) and getattr(slot, "ndim", 0) >= 4:
                span = int(np.prod(slot.shape[-2:]))
            else:
                continue
            group = _pick_group(span)
            if not group:
                pad_to = None
                group = None
            break
    wires: List[KVWire] = []
    all_jobs: List[Tuple[int, str, str, jnp.ndarray, int]] = []
    for ri, (bi, length) in enumerate(requests):
        slots, jobs = _extract_one(cache, bi, length, compress, pad_to)
        wires.append(KVWire(request_len=length, slots=slots))
        all_jobs.extend((ri, name, key, t, ln) for name, key, t, ln in jobs)
    # group by shape so each group is one kernel launch
    by_shape: Dict[Tuple[int, ...], List[int]] = {}
    for j, (_, _, _, t, _) in enumerate(all_jobs):
        by_shape.setdefault(tuple(t.shape), []).append(j)
    for idxs in by_shape.values():
        wts = _quantize_stacked([all_jobs[j][3] for j in idxs], backend,
                                group=group)
        for j, wt in zip(idxs, wts):
            ri, name, key, _, ln = all_jobs[j]
            wires[ri].slots[name][key] = _trim_wire_tensor(wt, ln)
    return wires


def compress_wire(wire: KVWire, *, backend: str = "auto") -> KVWire:
    """int4-quantize a raw wire's positional tensors (one kernel launch
    per distinct shape), leaving recurrent-state snapshots raw.

    Quantizes with position-aligned groups — the same layout the
    padded-extract path produces — so the result of compressing a
    spliced chunked-prefill wire is bit-identical to a one-shot
    bucketed extraction of the same cache values, and paged decode
    engines can scatter its rows zero-copy."""
    with tracing.span("wire", lambda: {"requests": 1, "pad_to": 0}):
        return _compress_wire(wire, backend)


def _compress_wire(wire: KVWire, backend: str) -> KVWire:
    slots: Dict[str, Dict[str, WireTensor]] = {}
    jobs: List[Tuple[str, str, jnp.ndarray]] = []
    for name, slot_wire in wire.slots.items():
        out: Dict[str, WireTensor] = {}
        for key, wt in slot_wire.items():
            if (wt.kind == "raw" and len(wt.orig_shape) == 4
                    and _pick_group(int(np.prod(wt.orig_shape[-2:])))):
                jobs.append((name, key, jnp.asarray(wt.payload["x"])))
            else:
                out[key] = wt
        slots[name] = out
    by_shape: Dict[Tuple[int, ...], List[int]] = {}
    for j, (_, _, t) in enumerate(jobs):
        by_shape.setdefault(tuple(t.shape), []).append(j)
    for shape, idxs in by_shape.items():
        group = _pick_group(int(np.prod(shape[-2:])))
        wts = _quantize_stacked([jobs[j][2] for j in idxs], backend,
                                group=group)
        for j, wt in zip(idxs, wts):
            name, key, _ = jobs[j]
            slots[name][key] = wt
    return KVWire(request_len=wire.request_len, slots=slots)


def concat_wires(wires: Sequence[KVWire], *,
                 backend: str = "auto") -> KVWire:
    """Splice per-chunk wires along the POSITION axis into one wire equal
    to a single extraction over the union of positions (chunked prefill).

    int4 payloads concatenate WITHOUT dequantizing: the padded-extract
    path quantizes with position-aligned groups (row ``t*ppr + r`` holds
    token ``t``'s ``r``-th group — the same layout contract that makes
    paged wire inserts zero-copy), so chunk boundaries are also group-row
    boundaries and the packed/scale/zero rows simply stack. Raw
    attention tensors concatenate on the position axis; a MIXED run
    (int4 prefix-cache wire + raw chunk wires) dequantizes the int4
    parts and splices raw — exactly the values a suffix prefill attends
    over either way. Recurrent-state snapshots are not positional and
    cannot be spliced (ValueError) — chunked prefill is gated on
    ``PrefillEngine.supports_suffix``, which excludes them."""
    wires = list(wires)
    if not wires:
        raise ValueError("concat_wires needs at least one wire")
    if len(wires) == 1:
        return wires[0]
    slots: Dict[str, Dict[str, WireTensor]] = {}
    for name in wires[0].slots:
        out: Dict[str, WireTensor] = {}
        for key in wires[0].slots[name]:
            wts = [w.slots[name][key] for w in wires]
            kinds = {wt.kind for wt in wts}
            lens = [wt.orig_shape[1] for wt in wts]
            if kinds == {"int4"}:
                L, _, Hkv, hd = wts[0].orig_shape
                g2 = wts[0].payload["packed"].shape[1]
                ppr = (Hkv * hd) // (2 * g2)      # quant rows per position
                payload = {}
                for pk in wts[0].payload:
                    parts = []
                    for wt, ln in zip(wts, lens):
                        a = jnp.asarray(wt.payload[pk])
                        parts.append(a.reshape(L, ln, ppr, a.shape[1]))
                    cat = jnp.concatenate(parts, axis=1)
                    payload[pk] = cat.reshape(-1, cat.shape[3])
                out[key] = WireTensor("int4", payload,
                                      (L, sum(lens), Hkv, hd),
                                      wts[0].dtype)
            elif (kinds <= {"raw", "int4"}
                  and all(len(wt.orig_shape) == 4 for wt in wts)):
                cat = jnp.concatenate(
                    [_dequantize(wt, backend) for wt in wts], axis=1)
                out[key] = WireTensor("raw", {"x": cat}, tuple(cat.shape),
                                      next(wt.dtype for wt in wts
                                           if wt.kind == "raw"))
            else:
                raise ValueError(
                    f"cannot splice wire slot {name}/{key}: "
                    f"kinds={sorted(kinds)}, shapes="
                    f"{[wt.orig_shape for wt in wts]} (recurrent-state "
                    f"snapshots are not positional)")
        slots[name] = out
    return KVWire(request_len=sum(w.request_len for w in wires),
                  slots=slots)


def dequantize_prefix_batch(wires: Sequence[KVWire], pad_to: int, *,
                            backend: str = "auto"):
    """Stack per-request prefix wires into ``transformer.prefill_suffix``
    inputs: a ``{slot: (k, v)}`` pytree of ``(L, B, pad_to, Hkv, hd)``
    bf16 arrays (prefix padded on the right; padding is masked by the
    suffix attention's ``prefix_len``) plus the ``(B,)`` true prefix
    lengths. One dequant per tensor; everything stays on device."""
    out: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]] = {}
    lens = jnp.asarray([w.request_len for w in wires], jnp.int32)
    for name in wires[0].slots:
        stacked = []
        for key in ("k", "v"):
            ts = []
            for w in wires:
                t = _dequantize(w.slots[name][key], backend)  # (L,ln,Hkv,hd)
                if t.shape[1] < pad_to:
                    t = jnp.pad(t, ((0, 0), (0, pad_to - t.shape[1]),
                                    (0, 0), (0, 0)))
                ts.append(t[:, :pad_to])
            stacked.append(jnp.stack(ts, axis=1))
        out[name] = (stacked[0], stacked[1])
    return out, lens


def insert(cache, wire: KVWire, batch_index: int, *, backend: str = "auto"):
    """Insert a transferred request state into a decode cache pytree."""
    return insert_batch(cache, [(wire, batch_index)], backend=backend)


def insert_batch(cache, items: Sequence[Tuple[KVWire, int]], *,
                 backend: str = "auto"):
    """Insert several (wire, slot_index) pairs, batching dequantization:
    one ``ops.kv_dequant`` call per distinct packed shape across all wires
    and layers."""
    # 1. collect + batch-dequantize all int4 tensors
    jobs: List[Tuple[int, str, str, WireTensor]] = []
    for wi, (wire, _) in enumerate(items):
        for name, slot_wire in wire.slots.items():
            for key, wt in slot_wire.items():
                if wt.kind == "int4":
                    jobs.append((wi, name, key, wt))
    deq: Dict[Tuple[int, str, str], jnp.ndarray] = {}
    by_shape: Dict[Tuple[Tuple[int, ...], Tuple[int, ...]], List[int]] = {}
    for j, (_, _, _, wt) in enumerate(jobs):
        k = (tuple(wt.payload["packed"].shape), tuple(wt.orig_shape))
        by_shape.setdefault(k, []).append(j)
    for (pshape, oshape), idxs in by_shape.items():
        packed = jnp.concatenate(
            [jnp.asarray(jobs[j][3].payload["packed"]) for j in idxs], axis=0)
        scale = jnp.concatenate(
            [jnp.asarray(jobs[j][3].payload["scale"]) for j in idxs], axis=0)
        zero = jnp.concatenate(
            [jnp.asarray(jobs[j][3].payload["zero"]) for j in idxs], axis=0)
        out = ops.kv_dequant(packed, scale, zero, backend=backend)
        rows = pshape[0]
        for t, j in enumerate(idxs):
            wi, name, key, wt = jobs[j]
            deq[(wi, name, key)] = out[t * rows:(t + 1) * rows].reshape(oshape)

    # 2. scatter into the cache (lazy device ops; no host sync)
    for wi, (wire, batch_index) in enumerate(items):
        L = wire.request_len
        for name, slot_wire in wire.slots.items():
            slot = cache[name]
            if "k" in slot_wire:
                k = deq.get((wi, name, "k"))
                if k is None:
                    k = _dequantize(slot_wire["k"], backend)
                v = deq.get((wi, name, "v"))
                if v is None:
                    v = _dequantize(slot_wire["v"], backend)
                s_cache = slot["k"].shape[2]
                upd = min(L, s_cache)
                cache[name]["k"] = slot["k"].at[:, batch_index, :upd].set(
                    k[:, -upd:].astype(slot["k"].dtype))
                cache[name]["v"] = slot["v"].at[:, batch_index, :upd].set(
                    v[:, -upd:].astype(slot["v"].dtype))
            elif "x" in slot_wire and not isinstance(slot, dict):
                # flat decoder KV (whisper); cross_* carries its own length
                t = deq.get((wi, name, "x"))
                if t is None:
                    t = _dequantize(slot_wire["x"], backend)
                upd = min(t.shape[1], slot.shape[2])
                cache[name] = slot.at[:, batch_index, :upd].set(
                    t[:, -upd:].astype(slot.dtype))
            else:
                for key, wt in slot_wire.items():
                    st = _dequantize(wt, backend)
                    cache[name][key] = slot[key].at[:, batch_index].set(
                        st.astype(slot[key].dtype))
        cache["lengths"] = cache["lengths"].at[batch_index].set(L)
    return cache


def wire_bytes_uncompressed(wire: KVWire) -> int:
    total = 0
    for s in wire.slots.values():
        for t in s.values():
            if t.kind == "int4":
                n = int(np.prod(t.orig_shape))
                total += n * 2
            else:
                total += t.nbytes()
    return total
