"""Host-side page allocator for the paged decode cache + KV-wire insertion.

The device-side page format lives in ``models/paged.py``; this module owns
the HOST bookkeeping: which pages are free, which slot owns which pages,
occupancy/fragmentation stats, and the scatter of an arriving ``KVWire``
into a slot's freshly allocated pages.

Key property (the reason the wire and the pages share one quantization
layout): a wire produced by the bucketed-prefill fast path
(``kv_transfer.extract_batch(pad_to=...)``) carries POSITION-ALIGNED int4
groups — row ``t*ppr + r`` is token ``t``'s r-th group, exactly a page's
row order — so insertion is a pure uint8/f32 scatter (packed rows into
``kp``/``vp``, each row's scale and zero into its lane of the page's
``sz`` rows; ``models/paged.py`` has the layout): **no dequantization,
no requantization, no 16-bit materialization**. Wires with position-
spanning groups (exact-length extracts pick the group from the flattened
size) or raw payloads are re-encoded into the page layout on arrival (one
dequant+quant, still never touching the dense cache layout).

Page 0 is reserved as the TRASH page (see ``models/paged.py``); the
allocator never hands it out.

The :class:`PagePool` allocator itself is PURE PYTHON — the jax/numpy
imports the insertion/extraction functions need are deferred into those
functions, so the model checker (``repro.analysis.modelcheck``, tier-1
CI) can drive the REAL refcount protocol in an image with no accelerator
stack installed.
"""
from __future__ import annotations

import functools
from typing import TYPE_CHECKING, Dict, Hashable, List, Optional, \
    Sequence, Set, Tuple

if TYPE_CHECKING:       # heavy imports stay lazy (see module docstring)
    from repro.serving.kv_transfer import KVWire, WireTensor


class PagePool:
    """Fixed-size-page allocator over ``num_pages`` pages (page 0 is the
    trash page and is never allocated). LIFO free list: a released
    request's pages are the next handed out, which keeps the hot page set
    small.

    Pages are REFCOUNTED: ``alloc`` grants the first reference,
    ``share`` adds one for a new owner (prefix-cache sharing), and
    ``free``/``unshare`` drop one owner's reference — a page returns to
    the free list only when its last owner releases it. Owner tags are
    arbitrary hashables (slot indices, the prefix cache's tag, in-flight
    pin tags); an owner holds at most one reference per page. All
    refcount/free-list mutation lives HERE (lint rule R006) — everything
    else goes through this API.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the trash page)")
        self.num_pages = num_pages
        self.page_size = page_size
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._owners: Dict[int, Set[Hashable]] = {}   # page -> owner tags
        self._by_owner: Dict[Hashable, Set[int]] = {}  # owner -> its pages
        # stats
        self.allocs = 0
        self.frees = 0
        self.shares = 0
        self.unshares = 0
        self.alloc_failures = 0
        self.peak_in_use = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_in_use(self) -> int:
        return len(self._owners)

    @property
    def n_shared(self) -> int:
        """Pages currently held by more than one owner."""
        return sum(1 for s in self._owners.values() if len(s) > 1)

    @property
    def capacity(self) -> int:
        """Allocatable pages (total minus the trash page)."""
        return self.num_pages - 1

    def owned_by(self, owner: Hashable) -> List[int]:
        """Pages this owner holds a reference on — O(pages held), via the
        per-owner index (not a scan of the whole pool)."""
        return sorted(self._by_owner.get(owner, ()))

    def owners_of(self, page: int) -> frozenset:
        return frozenset(self._owners.get(page, frozenset()))

    def refcount(self, page: int) -> int:
        return len(self._owners.get(page, ()))

    def pages_in_use(self) -> List[int]:
        return sorted(self._owners)

    def _grant(self, page: int, owner: Hashable):
        self._owners.setdefault(page, set()).add(owner)
        self._by_owner.setdefault(owner, set()).add(page)

    def _revoke(self, page: int, owner: Hashable) -> bool:
        """Drop one owner's reference; True when the page became free."""
        owners = self._owners[page]
        owners.discard(owner)
        held = self._by_owner.get(owner)
        if held is not None:
            held.discard(page)
            if not held:
                del self._by_owner[owner]
        if owners:
            return False
        del self._owners[page]
        self._free.append(page)
        return True

    def alloc(self, n: int, owner: Hashable) -> Optional[List[int]]:
        """Take ``n`` pages for ``owner`` (a slot index); None if the pool
        cannot satisfy the request — all-or-nothing, no partial grants."""
        if n <= 0:
            return []
        if n > len(self._free):
            self.alloc_failures += 1
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._grant(p, owner)
        self.allocs += n
        self.peak_in_use = max(self.peak_in_use, self.n_in_use)
        return pages

    def share(self, pages: Sequence[int], owner: Hashable):
        """Add ``owner``'s reference to already-allocated pages (prefix
        sharing). Batch-validated before any mutation: every page must be
        in use and not already held by this owner."""
        seen: Set[int] = set()
        for p in pages:
            if p in seen:
                raise ValueError(f"page {p} listed twice in one share()")
            seen.add(p)
            owners = self._owners.get(p)
            if owners is None:
                raise ValueError(f"share of free/foreign page {p}")
            if owner in owners:
                raise ValueError(f"owner {owner!r} already holds page {p}")
        for p in pages:
            self._grant(p, owner)
        self.shares += len(seen)

    def free(self, pages: Sequence[int], owner: Optional[Hashable] = None):
        """Release ``owner``'s reference on each page; a page returns to
        the free list only when its refcount hits 0. With ``owner=None``
        (legacy single-owner call) each page must have exactly one owner.
        Validates the WHOLE batch before mutating anything, so a bad call
        (double free, page listed twice, page not held by ``owner``)
        raises without corrupting the free list with a partial free."""
        seen: Set[int] = set()
        for p in pages:
            if p in seen:
                raise ValueError(f"page {p} listed twice in one free()")
            seen.add(p)
            owners = self._owners.get(p)
            if owners is None:
                raise ValueError(f"double free / foreign page {p}")
            if owner is None:
                if len(owners) != 1:
                    raise ValueError(
                        f"page {p} is shared by {sorted(map(repr, owners))}; "
                        f"free() needs an explicit owner")
            elif owner not in owners:
                if len(owners) == 1:
                    raise ValueError(
                        f"page {p} is owned by slot {next(iter(owners))}, "
                        f"not {owner!r}")
                raise ValueError(
                    f"page {p} is shared by {sorted(map(repr, owners))}; "
                    f"{owner!r} holds no reference")
        for p in pages:
            o = owner if owner is not None else next(iter(self._owners[p]))
            if self._revoke(p, o):
                self.frees += 1
            else:
                self.unshares += 1

    def unshare(self, pages: Sequence[int], owner: Hashable):
        """Drop ``owner``'s reference on shared pages — same release path
        as :meth:`free` (a page whose last reference drops goes back to
        the free list), with the owner always explicit."""
        self.free(pages, owner=owner)

    def snapshot(self):
        """Opaque deep copy of the allocator state (free list, refcount
        maps, counters) — with :meth:`restore`, the ONE sanctioned way to
        branch/rewind pool state from outside this module (rule R006:
        the model checker forks states without touching internals)."""
        return (list(self._free),
                {p: set(o) for p, o in self._owners.items()},
                {o: set(p) for o, p in self._by_owner.items()},
                (self.allocs, self.frees, self.shares, self.unshares,
                 self.alloc_failures, self.peak_in_use))

    def restore(self, snap):
        """Rewind to a :meth:`snapshot` (same pool geometry assumed)."""
        free, owners, by_owner, counters = snap
        self._free = list(free)
        self._owners = {p: set(o) for p, o in owners.items()}
        self._by_owner = {o: set(p) for o, p in by_owner.items()}
        (self.allocs, self.frees, self.shares, self.unshares,
         self.alloc_failures, self.peak_in_use) = counters

    def canonicalize(self):
        """Sort the free list into a fixed order. Free-list order is
        semantically irrelevant (any free page serves any alloc) but LIFO
        recycling makes :meth:`state_key` distinguish states that differ
        only by allocation history — the model checker calls this after
        every event as a symmetry reduction, collapsing those
        permutations so its search closes."""
        self._free.sort()

    def state_key(self) -> Tuple:
        """Hashable identity of the allocator state (owner tags by repr)
        — the model checker's visited-state key."""
        return (tuple(self._free),
                tuple(sorted((p, tuple(sorted(repr(o) for o in owners)))
                             for p, owners in self._owners.items())))

    def occupancy(self) -> float:
        return self.n_in_use / max(self.capacity, 1)

    def stats(self) -> Dict[str, float]:
        return {"pages": self.capacity, "page_size": self.page_size,
                "in_use": self.n_in_use, "free": self.n_free,
                "occupancy": self.occupancy(),
                "peak_in_use": self.peak_in_use, "allocs": self.allocs,
                "frees": self.frees, "shares": self.shares,
                "unshares": self.unshares, "shared_pages": self.n_shared,
                "alloc_failures": self.alloc_failures}


def pages_needed(n_tokens: int, page_size: int) -> int:
    return max(1, -(-n_tokens // page_size))


def _wire_rows_aligned(wt: WireTensor, g: int, ppr: int) -> bool:
    """True when an int4 wire tensor's quantization rows map 1:1 onto page
    rows: same group width, groups never straddling token positions."""
    if wt.kind != "int4":
        return False
    from repro.serving.kv_transfer import PaddedRows
    L, ln, Hkv, hd = wt.orig_shape
    if isinstance(wt.payload, PaddedRows):
        L, ln = wt.payload.L, wt.payload.lp
        packed = wt.payload.rows["packed"]
    else:
        packed = wt.payload["packed"]
    return (packed.shape[1] == g // 2
            and packed.shape[0] == L * ln * ppr)


def _wire_to_rows(wt: WireTensor, cfg, backend: str):
    """Return (packed, scale, zero) rows in page row-order for one wire
    tensor, the positions they cover (a padded wire's whole bucket) and
    whether they came zero-copy — otherwise re-encoded via one
    dequant+quant (device ops, no host sync)."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models import paged
    from repro.serving.kv_transfer import PaddedRows, _dequantize
    g = paged.page_group(cfg)
    ppr = paged.groups_per_token(cfg)
    if _wire_rows_aligned(wt, g, ppr):
        if isinstance(wt.payload, PaddedRows):
            rows, n_pos = wt.payload.rows, wt.payload.lp
        else:
            rows, n_pos = wt.payload, wt.orig_shape[1]
        return (tuple(jnp.asarray(rows[k])
                      for k in ("packed", "scale", "zero")), n_pos, True)
    dense = _dequantize(wt, backend)               # (L, ln, Hkv, hd)
    L, ln = dense.shape[:2]
    rows = dense.reshape(L * ln * ppr, g)
    return ops.kv_quant(rows, backend=backend), ln, False


@functools.lru_cache(maxsize=None)
def _scatter_rows_fn():
    """The jitted scatter of one layer slot's wire rows into a slot's
    pages: ``vals`` maps ``"k"``/``"v"`` to (packed, scale, zero) rows
    covering ``n_pos`` positions; packed rows go to ``kp``/``vp``, scale
    and zero to their rows of ``sz`` (``paged.SZ_ROWS``). Positions from
    ``ln`` on (a padded bucket's tail) index past the pool and are
    dropped, so ``ln`` is a traced scalar: one program per bucket shape,
    not per prompt length."""
    import jax
    import jax.numpy as jnp

    from repro.models import paged

    def scatter(pool, vals, pages, ln, page_size):
        L, n_pages, R = pool["kp"].shape[:3]
        ppr = R // page_size
        out = dict(pool)
        for base, (packed, scale, zero) in vals.items():
            n_pos = packed.shape[0] // (L * ppr)
            tpos = jnp.arange(n_pos)
            pg = jnp.where(tpos < ln, pages[tpos // page_size],
                           n_pages)[:, None]                   # (n_pos, 1)
            rows = ((tpos % page_size)[:, None] * ppr
                    + jnp.arange(ppr)[None])                   # (n_pos, ppr)
            key = base + "p"
            out[key] = out[key].at[:, pg, rows].set(
                packed.reshape(L, n_pos, ppr, -1).astype(out[key].dtype),
                mode="drop")
            sz = jnp.stack([a.reshape(L, n_pos, ppr) for a in (scale, zero)],
                           axis=1)                      # (L, 2, n_pos, ppr)
            which = jnp.asarray(paged.SZ_ROWS[base])[:, None, None]
            out["sz"] = out["sz"].at[:, pg, which, rows].set(
                sz.astype(out["sz"].dtype), mode="drop")
        return out
    return jax.jit(scatter, static_argnames=("page_size",))


def insert_wires(cache, cfg, items: Sequence[Tuple], *,
                 backend: str = "auto"):
    """Scatter transferred requests into their allocated pages.

    ``items`` = (wire, slot_index, pages) with ``pages`` already allocated
    by the :class:`PagePool` (``len(pages) >= ceil(len/page_size)``; the
    tail of the last page absorbs decode appends). A 4th element
    ``prefix_pages`` SPLICES the wire onto a shared, page-aligned prefix
    chain: the wire carries only the suffix (token 0 of the wire is
    absolute position ``len(prefix_pages) * page_size``), the page-table
    row becomes ``prefix_pages + pages``, and lengths cover the whole
    chain. Prefix pages are never written — only the suffix scatter and
    decode's tail appends touch pages here. Updates page-table rows and
    lengths. Returns (cache, n_zero_copy, n_reencoded) — the counters
    feed the bench's zero-dequant claim."""
    import jax

    from repro.serving import tracing
    with tracing.span("page_insert", lambda: {"wires": len(items)}), \
            jax.named_scope("page_insert"):
        return _insert_wires(cache, cfg, items, backend)


def _insert_wires(cache, cfg, items: Sequence[Tuple], backend: str):
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.kv_transfer import _dequantize
    int4 = "kp" in cache["slot0"]
    ps = cache_page_size(cache, cfg)
    W = cache["page_table"].shape[1]
    n_zero, n_reenc = 0, 0
    for item in items:
        wire, slot, pages = item[0], item[1], item[2]
        prefix = list(item[3]) if len(item) > 3 and item[3] else []
        prefix_len = len(prefix) * ps
        ln = wire.request_len
        need = pages_needed(ln, ps)
        if len(pages) < need or len(prefix) + need > W:
            raise ValueError(
                f"slot {slot}: {len(pages)} page(s) for a {ln}-token wire "
                f"(needs {need}, prefix {len(prefix)}, table width {W})")
        tpos = np.arange(ln)
        dst_page = np.asarray(pages, np.int32)[tpos // ps]          # (ln,)
        for name, slot_wire in wire.slots.items():
            buf = cache[name]
            if int4:
                vals, n_pos = {}, 0
                for key, wt in slot_wire.items():
                    rows, n, aligned = _wire_to_rows(wt, cfg, backend)
                    vals[key] = rows
                    n_pos = max(n_pos, n)
                    n_zero += int(aligned)
                    n_reenc += int(not aligned)
                n_pg = -(-n_pos // ps)
                pg = np.zeros((n_pg,), np.int32)
                pg[:min(n_pg, len(pages))] = pages[:n_pg]
                cache[name] = _scatter_rows_fn()(buf, vals, jnp.asarray(pg),
                                                 ln, page_size=ps)
                continue
            for key, wt in slot_wire.items():
                dense = _dequantize(wt, backend)         # (L, ln, Hkv, hd)
                dst = buf[key]
                cache[name][key] = dst.at[
                    :, dst_page, tpos % ps].set(dense.astype(dst.dtype))
        row = np.zeros((W,), np.int32)                   # rest -> trash
        chain = prefix + list(pages)
        row[:len(chain)] = chain
        cache["page_table"] = cache["page_table"].at[slot].set(
            jnp.asarray(row))
        cache["lengths"] = cache["lengths"].at[slot].set(prefix_len + ln)
    return cache, n_zero, n_reenc


def set_page_chain(cache, slot: int, pages: Sequence[int], length: int):
    """Point a slot's page-table row at an existing page chain (the full
    prefix-hit admission: every token is already resident, nothing is
    scattered). Row tail stays at the trash page."""
    import jax.numpy as jnp
    import numpy as np
    W = cache["page_table"].shape[1]
    if len(pages) > W:
        raise ValueError(f"chain of {len(pages)} pages exceeds table "
                         f"width {W}")
    row = np.zeros((W,), np.int32)
    row[:len(pages)] = pages
    cache["page_table"] = cache["page_table"].at[slot].set(jnp.asarray(row))
    cache["lengths"] = cache["lengths"].at[slot].set(length)
    return cache


def copy_page(cache, src_page: int, dst_page: int):
    """Device-copy one page's contents across every layer tensor (packed
    rows and the ``sz`` scale/zero lanes alike: the page is axis 1 of
    each) — the copy-on-write step before a slot appends to a page it
    shares. Pure data movement; refcount bookkeeping stays in
    :class:`PagePool`."""
    for name, buf in cache.items():
        if name in ("page_table", "lengths"):
            continue
        for key, a in buf.items():
            cache[name][key] = a.at[:, dst_page].set(a[:, src_page])
    return cache


def extract_slot_wire(cache, cfg, ln: int, pages: Sequence[int],
                      ) -> KVWire:
    """Gather one resident slot's pages back into a :class:`KVWire`
    (the decode->decode migration path of ``Gateway.handle_preemption``).

    For the int4 residency this is a pure page gather — the pages already
    hold the wire's position-aligned group encoding (packed rows from
    ``kp``/``vp``, scale and zero from their ``sz`` lanes), so the produced
    tensors pass ``_wire_rows_aligned`` and scatter zero-copy into the
    destination pool (no dequant/requant round-trip in either direction).
    The bf16 residency ships raw tokens. Tokens appended by the in-loop
    decode quantizer extract bit-identically to wire-inserted ones
    (``models/paged.py`` keeps the two paths on the same kernel math).
    """
    import numpy as np

    from repro.models import paged
    from repro.serving.kv_transfer import KVWire, WireTensor
    int4 = "kp" in cache["slot0"]
    ps = cache_page_size(cache, cfg)
    ppr = paged.groups_per_token(cfg)
    g = paged.page_group(cfg)
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    if pages_needed(ln, ps) > len(pages):
        raise ValueError(f"{ln}-token slot spans more than {len(pages)} "
                         f"page(s)")
    pg = np.asarray(pages, np.int32)
    slots: Dict[str, Dict[str, WireTensor]] = {}
    for name, buf in cache.items():
        if name in ("page_table", "lengths"):
            continue
        out: Dict[str, WireTensor] = {}
        for key in ("k", "v"):
            if int4:
                a = buf[key + "p"][:, pg]                # (L, n_pg, R, g/2)
                sz = buf["sz"][:, pg]                    # (L, n_pg, 4, R)
                L = a.shape[0]
                cut = lambda x, w: x.reshape(
                    L, len(pages) * ps, ppr, w)[:, :ln].reshape(-1, w)
                s_row, z_row = paged.SZ_ROWS[key]
                payload = {"packed": cut(a, g // 2),
                           "scale": cut(sz[:, :, s_row], 1),
                           "zero": cut(sz[:, :, z_row], 1)}
                out[key] = WireTensor("int4", payload, (L, ln, Hkv, hd))
            else:
                a = buf[key][:, pg]                  # (L, n_pg, ps, Hkv, hd)
                L = a.shape[0]
                t = a.reshape(L, len(pages) * ps, Hkv, hd)[:, :ln]
                out[key] = WireTensor("raw", {"x": t}, tuple(t.shape),
                                      str(t.dtype))
        slots[name] = out
    return KVWire(request_len=ln, slots=slots)


def release_slot(cache, slot: int):
    """Point a released slot's table row back at the trash page and zero
    its length (the pages themselves go back through ``PagePool.free``)."""
    cache["page_table"] = cache["page_table"].at[slot].set(0)
    cache["lengths"] = cache["lengths"].at[slot].set(0)
    return cache


def cache_page_size(cache, cfg) -> int:
    """Recover page_size from the cache shapes (token rows per page)."""
    from repro.models import paged
    slot = cache["slot0"]
    if "kp" in slot:
        return slot["kp"].shape[2] // paged.groups_per_token(cfg)
    return slot["k"].shape[2]
