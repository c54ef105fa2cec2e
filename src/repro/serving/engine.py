"""Serving engines: a prefill engine and a continuous-batching decode engine
around one model replica each (the in-process realization of the paper's
"model serving group").

The decode engine owns a slotted cache (capacity = max_slots sequences);
requests join/leave slots between steps — classic continuous batching without
page tables (TPU-idiomatic fixed layout + length masks, see DESIGN.md §2).

Perf architecture (DESIGN.md §3): the decode hot loop is DEVICE-RESIDENT —
``step()`` runs a jitted ``lax.scan`` over ``chunk_size`` decode steps and
pays one host synchronization per *chunk* instead of per token. Prefill
compilation is bounded by power-of-two length buckets, so the jit cache
holds at most ``log2(max_seq)`` entries per engine.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import paged_attention
from repro.models import paged as paged_fmt
from repro.models import registry
from repro.serving import kv_transfer, page_pool, protocol, tracing
from repro.serving.kv_transfer import KVWire
from repro.serving.page_pool import PagePool, pages_needed
from repro.serving.prefix_cache import PrefixCache, PrefixMatch


@dataclass
class GenRequest:
    """Engine-level unit of work: prompt in, ``out_tokens`` accumulate.

    Engines never stamp the timestamp fields — they belong to the owning
    ``RequestHandle`` (``gateway.py``)."""
    rid: int
    tokens: np.ndarray              # prompt token ids (1D)
    max_new_tokens: int
    extras: Dict[str, np.ndarray] = field(default_factory=dict)
    out_tokens: List[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_first: float = -1.0
    t_done: float = -1.0
    # prefix-cache partial hit: positions [0, start_pos) are already
    # resident on the target decode replica (``prefix_pages``); prefill
    # covers only the suffix, attending over ``prefix_wire``'s KV
    start_pos: int = 0
    prefix_pages: Optional[List[int]] = None
    prefix_wire: Optional[KVWire] = None
    prefix_replica: int = -1


# -- unified admission API ----------------------------------------------------
#
# Every way a request can reach a decode replica is ONE call —
# ``DecodeEngine.admit(AdmissionBatch)`` — with a typed per-item source
# instead of four near-identical entry points (DESIGN.md §5):
#
#   FRESH       one-shot prefill wire; ``token`` is the first output
#   CHUNKED     concatenated chunked-prefill wire; engine-side identical
#               to FRESH (the wire already covers the whole prompt suffix)
#   PREFIX_HIT  full prefix-cache hit; no wire — ``pages`` is the resident
#               chain and ``token`` the known first output
#   MIGRATED    mid-stream snapshot off a preempted replica; ``token`` is
#               the RESUME token (already in ``out_tokens`` at the source,
#               so it is not re-appended)

ADMIT_FRESH = "FRESH"
ADMIT_CHUNKED = "CHUNKED"
ADMIT_PREFIX_HIT = "PREFIX_HIT"
ADMIT_MIGRATED = "MIGRATED"


@dataclass
class AdmissionItem:
    """One request entering continuous batching, tagged with how its KV
    arrives. ``wire`` for FRESH/CHUNKED/MIGRATED; ``pages`` for
    PREFIX_HIT."""
    req: GenRequest
    token: int
    source: str = ADMIT_FRESH
    wire: Optional[KVWire] = None
    pages: Optional[List[int]] = None


@dataclass
class AdmissionBatch:
    """Ordered admission attempt; admission is FIFO and stops at the
    first item the page/slot budget cannot place — ``admit`` returns the
    rejected tail as another ``AdmissionBatch``."""
    items: List[AdmissionItem] = field(default_factory=list)

    def __len__(self) -> int:
        return len(self.items)

    def __iter__(self):
        return iter(self.items)

    def __bool__(self) -> bool:
        return bool(self.items)


@dataclass
class PartialPrefill:
    """Resumable chunked-prefill state (SARATHI-style).

    ``pos`` counts prompt tokens prefilled so far PAST the request's
    ``start_pos`` (prefix-cache-resident tokens are never re-prefilled);
    ``wires`` holds one RAW (uncompressed) suffix wire per completed
    chunk. Chunk N+1 is a suffix prefill attending over the concatenation
    of the request's prefix wire (if any) and the accumulated chunk wires
    — the PR-8 machinery. The chunk wires stay raw so the resumable
    prefix is the EXACT float KV the one-shot prefill would compute
    (int4 round-tripping it perturbs near-tie logits and breaks token
    parity); quantization happens ONCE, over the spliced whole, when the
    job completes — which also makes the ``transport`` wire bit-identical
    to a one-shot extraction. Once ``done``, ``first`` is the argmax at
    the prompt's true last position and :meth:`wire` returns the
    admission wire."""
    req: GenRequest
    pos: int = 0
    wires: List[KVWire] = field(default_factory=list)
    first: int = -1
    done: bool = False
    transport: Optional[KVWire] = None

    @property
    def next_pos(self) -> int:
        return self.req.start_pos + self.pos

    @property
    def remaining(self) -> int:
        return len(self.req.tokens) - self.next_pos

    def wire(self) -> KVWire:
        if self.transport is not None:
            return self.transport
        return kv_transfer.concat_wires(self.wires)


def _next_pow2(n: int) -> int:
    return 1 << max((n - 1).bit_length(), 0)


def _sanitize_enabled() -> bool:
    """``REPRO_SANITIZE=1`` swaps the page pool for the allocation-site-
    tracking variant and audits migrated wires (repro.analysis)."""
    return os.environ.get("REPRO_SANITIZE", "").strip() not in (
        "", "0", "false", "no")


class PrefillEngine:
    """Latency-oriented: processes one prompt batch at a time.

    Prompts are right-padded to power-of-two length buckets (causal
    attention makes the padding invisible to real positions; logits are
    gathered at each prompt's own last token), so the engine compiles at
    most ``log2(max_seq)`` length buckets (times a handful of pow2 batch
    widths <= max_batch) instead of one variant per unique prompt shape. Architectures with recurrent state (SSM / hybrid /
    xLSTM) or sliding-window attention fall back to exact-length grouping:
    their prefill state depends on every processed position, so padding
    would corrupt it.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_seq: int = 512,
                 rt=None, bucket: bool = True, max_batch: int = 4,
                 min_bucket: int = 16):
        self.cfg = cfg
        self.params = params
        self.api = registry.build(cfg, rt=rt)
        self.max_seq = max_seq
        self.max_batch = max_batch
        self.min_bucket = min_bucket
        mixes = ({"attn"} if cfg.family == "audio" else
                 {k.split("+")[0] for k in cfg.layer_kinds()})
        self.bucketed = (bucket and mixes == {"attn"}
                         and not cfg.sliding_window
                         and cfg.family != "vlm")
        self._jits: Dict[Tuple[int, int], Callable] = {}
        # suffix prefill (prefix-cache partial hits): keyed by
        # (batch, suffix_bucket, prefix_bucket) so the jit cache stays
        # log2-bounded in both lengths
        self._suffix_jits: Dict[Tuple[int, int, int], Callable] = {}
        # device work under the ``prefill`` scope; the jitted programs
        # keep their names (``jit__lambda``)
        self._prefill = tracing.scoped("prefill", self.api.prefill)
        self._suffix = (tracing.scoped("prefill", self.api.prefill_suffix)
                        if self.api.prefill_suffix is not None else None)
        # prompt tokens prefilled, and the padded tokens the model ran
        self.true_tokens = 0
        self.bucket_tokens = 0

    @property
    def supports_suffix(self) -> bool:
        """True when this engine can prefill a suffix against resident
        prefix KV (pure-attention stack, bucketed padding)."""
        return self.bucketed and self.api.prefill_suffix is not None

    def _prefill_fn(self, batch_shape: Tuple[int, int]) -> Callable:
        if batch_shape not in self._jits:
            self._jits[batch_shape] = jax.jit(
                lambda p, b: self._prefill(p, b, max_seq=self.max_seq))
        return self._jits[batch_shape]

    def _suffix_fn(self, key: Tuple[int, int, int]) -> Callable:
        if key not in self._suffix_jits:
            self._suffix_jits[key] = jax.jit(
                lambda p, b: self._suffix(p, b, max_seq=self.max_seq))
        return self._suffix_jits[key]

    @property
    def jit_cache_size(self) -> int:
        return len(self._jits) + len(self._suffix_jits)

    def _bucket_of(self, n: int) -> int:
        return min(max(_next_pow2(n), self.min_bucket), self.max_seq)

    def _batch_width(self, n: int) -> int:
        """Rows of a bucketed call of ``n`` prompts: a pow2 width, so a
        lone request doesn't pay a full-width forward pass and the jit
        cache only gains log2(max_batch) variants per length bucket."""
        return min(_next_pow2(n), self.max_batch)

    def run(self, reqs: List[GenRequest], *, compress: bool = True,
            backend: str = "auto") -> List[Tuple[GenRequest, KVWire, int]]:
        """Prefill a batch; returns per-request (req, wire, first_token).
        Requests carrying a resident prefix (``start_pos``/``prefix_wire``
        from a prefix-cache partial hit) take the suffix path: only
        ``tokens[start_pos:]`` run through the model, attending over the
        dequantized prefix KV; the returned wire covers the suffix."""
        if not reqs:
            return []
        launches = self._launches(reqs)
        counts = [self._launch_tokens(*launch) for launch in launches]
        true, padded = (sum(c[i] for c in counts) for i in (0, 1))
        self.true_tokens += true
        self.bucket_tokens += padded
        run_one = {"suffix": self._run_one_suffix_bucket,
                   "bucket": self._run_one_bucket,
                   "exact": self._run_one_exact}
        with tracing.span("prefill", lambda: {
                "rids": [r.rid for r in reqs], "batch": len(reqs),
                "true_tokens": true, "bucket_tokens": padded}):
            out = []
            for kind, key, group in launches:
                out.extend(run_one[kind](group, *key, compress=compress,
                                         backend=backend))
        return out

    def _launches(self, reqs: List[GenRequest]
                  ) -> List[Tuple[str, Tuple[int, ...], List[GenRequest]]]:
        """The model calls ``run`` makes, in order, as ``(kind, key,
        group)``: ``"suffix"`` prompts grouped by (suffix bucket, prefix
        bucket), then the rest as ``"bucket"`` (pow2 length bucket) or,
        for engines that cannot pad, ``"exact"`` (prompt length); bucketed
        groups split at ``max_batch``."""
        suffix = [r for r in reqs
                  if r.start_pos > 0 and r.prefix_wire is not None]
        sids = {id(r) for r in suffix}
        normal = [r for r in reqs if id(r) not in sids]
        if suffix and not self.supports_suffix:
            raise ValueError(
                "suffix prefill requested but this engine cannot "
                "slice its state at a position boundary")
        padded = suffix + (normal if self.bucketed else [])
        too_long = [r.rid for r in padded if len(r.tokens) > self.max_seq]
        if too_long:
            # fail loudly like the exact-length path does, instead of
            # silently conditioning on a truncated prompt
            raise ValueError(
                f"prompt(s) exceed max_seq={self.max_seq}: rids {too_long}")
        out = []
        for kind, rs in (("suffix", suffix),
                         ("bucket" if self.bucketed else "exact", normal)):
            groups: Dict[Tuple[int, ...], List[GenRequest]] = {}
            for r in rs:
                if kind == "suffix":
                    # prefix lengths are page-aligned by construction
                    key = (self._bucket_of(len(r.tokens) - r.start_pos),
                           self._bucket_of(r.start_pos))
                elif kind == "bucket":
                    key = (self._bucket_of(len(r.tokens)),)
                else:
                    key = (len(r.tokens),)
                groups.setdefault(key, []).append(r)
            for key, group in groups.items():
                width = len(group) if kind == "exact" else self.max_batch
                for lo in range(0, len(group), width):
                    out.append((kind, key, group[lo:lo + width]))
        return out

    def _launch_tokens(self, kind: str, key: Tuple[int, ...],
                       group: List[GenRequest]) -> Tuple[int, int]:
        """(prompt tokens, padded tokens the model computes) of one call:
        a bucketed call runs a pow2 batch of ``key[0]``-token rows."""
        if kind == "exact":
            return key[0] * len(group), key[0] * len(group)
        start = kind == "suffix"
        true = sum(len(r.tokens) - (r.start_pos if start else 0)
                   for r in group)
        return true, self._batch_width(len(group)) * key[0]

    def prefill_chunk(self, jobs: List[PartialPrefill], budget: int, *,
                      compress: bool = True, backend: str = "auto"
                      ) -> List[PartialPrefill]:
        """Advance chunked prefills by up to ``budget`` prompt tokens
        TOTAL across ``jobs`` (FIFO), one model call per tick.

        Each job's next chunk is a suffix prefill over the concat of its
        accumulated RAW chunk wires (plus any prefix-cache wire) — exact
        float KV, so the suffix attends over the same values a one-shot
        prefill computes in-cache and greedy decoding after chunking
        emits the SAME tokens. The admission wire is quantized once,
        over the spliced whole, when a job completes (``compress=True``),
        which is bit-identical to compressing a one-shot extraction.
        Engines that cannot slice state at a position boundary (recurrent
        state, SWA) run each job to completion in one shot — the budget
        degrades to an admission hint. Mutates and returns ``jobs``."""
        left = max(int(budget), 0)
        work = []                       # (job, clone, take)
        for job in jobs:
            if job.done or job.remaining <= 0 or left <= 0:
                continue
            take = protocol.chunk_take(job.remaining, left,
                                       self.supports_suffix)
            left -= min(take, left)
            req = job.req
            upto = job.next_pos + take
            clone = GenRequest(req.rid, np.asarray(req.tokens)[:upto],
                               req.max_new_tokens, extras=req.extras)
            if job.next_pos > 0:
                parts = ([req.prefix_wire]
                         if req.start_pos > 0 and req.prefix_wire is not None
                         else []) + job.wires
                clone.start_pos = job.next_pos
                clone.prefix_wire = kv_transfer.concat_wires(
                    parts, backend=backend)
            work.append((job, clone, take))
        if not work:
            return jobs
        by_clone = {id(c): (job, take) for job, c, take in work}
        # chunks extract RAW: the resumable prefix must be exact floats
        # (protocol.chunk_extract_compress() is False by contract)
        for clone, wire, first in self.run(
                [c for _, c, _ in work],
                compress=protocol.chunk_extract_compress(),
                backend=backend):
            job, take = by_clone[id(clone)]
            job.wires.append(wire)
            job.pos += take
            if protocol.chunk_complete(job.next_pos, len(job.req.tokens)):
                job.done = True
                job.first = int(first)
                full = kv_transfer.concat_wires(job.wires)
                job.transport = (kv_transfer.compress_wire(
                    full, backend=backend) if compress else full)
        return jobs

    def _run_one_exact(self, group, L, *, compress, backend):
        """Prompts of exactly ``L`` tokens (no padding ever enters
        attention); one jit entry per unique (batch, length) shape."""
        toks = np.stack([r.tokens for r in group]).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks)}
        for key in group[0].extras:
            batch[key] = jnp.stack(
                [jnp.asarray(r.extras[key]) for r in group])
        logits, cache = self._prefill_fn(toks.shape)(self.params, batch)
        first = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        wires = kv_transfer.extract_batch(
            cache, [(i, L) for i in range(len(group))],
            compress=compress, backend=backend)
        return [(r, wires[i], int(first[i])) for i, r in enumerate(group)]

    def _run_one_bucket(self, group, Lb, *, compress, backend):
        B = self._batch_width(len(group))
        lens = [min(len(r.tokens), Lb) for r in group]
        toks = np.zeros((B, Lb), np.int32)
        for i, r in enumerate(group):
            toks[i, :lens[i]] = r.tokens[:lens[i]]
        last_pos = np.zeros((B,), np.int32)
        last_pos[:len(group)] = np.asarray(lens) - 1
        true_len = np.ones((B,), np.int32)
        true_len[:len(group)] = lens
        batch = {"tokens": jnp.asarray(toks),
                 "last_pos": jnp.asarray(last_pos),
                 "true_len": jnp.asarray(true_len)}
        for key in (group[0].extras if group else {}):
            ex = [np.asarray(r.extras[key]) for r in group]
            ex += [ex[0]] * (B - len(group))     # dummy rows, discarded
            batch[key] = jnp.asarray(np.stack(ex))
        logits, cache = self._prefill_fn((B, Lb))(self.params, batch)
        first = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        wires = kv_transfer.extract_batch(
            cache, [(i, lens[i]) for i in range(len(group))],
            compress=compress, backend=backend, pad_to=Lb)
        return [(r, wires[i], int(first[i])) for i, r in enumerate(group)]

    def _run_one_suffix_bucket(self, group, Lb, Pb, *, compress, backend):
        B = self._batch_width(len(group))
        slens = [min(len(r.tokens) - r.start_pos, Lb) for r in group]
        toks = np.zeros((B, Lb), np.int32)
        for i, r in enumerate(group):
            toks[i, :slens[i]] = np.asarray(r.tokens)[r.start_pos:][:slens[i]]
        last_pos = np.zeros((B,), np.int32)
        last_pos[:len(group)] = np.asarray(slens) - 1
        true_len = np.ones((B,), np.int32)
        true_len[:len(group)] = slens
        prefix_kv, plen = kv_transfer.dequantize_prefix_batch(
            [r.prefix_wire for r in group], Pb, backend=backend)
        if B > len(group):
            # dummy batch rows: zero-length prefix, masked out entirely
            pad = B - len(group)
            prefix_kv = jax.tree.map(
                lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                  (a.ndim - 2)), prefix_kv)
            plen = jnp.pad(plen, (0, pad))
        batch = {"tokens": jnp.asarray(toks),
                 "last_pos": jnp.asarray(last_pos),
                 "true_len": jnp.asarray(true_len),
                 "prefix_kv": prefix_kv,
                 "prefix_len": plen}
        logits, cache = self._suffix_fn((B, Lb, Pb))(self.params, batch)
        first = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        wires = kv_transfer.extract_batch(
            cache, [(i, slens[i]) for i in range(len(group))],
            compress=compress, backend=backend, pad_to=Lb)
        return [(r, wires[i], int(first[i])) for i, r in enumerate(group)]


def paged_decode_jits(api, page_size: int, backend: str, *, donate: bool):
    """A paged decode engine's jitted single step and chunk, with the cache
    (argument 1) donated when ``donate``."""
    step_fn, chunk_fn = api.paged_decode_fns(page_size, backend)
    donated = (1,) if donate else ()
    return (jax.jit(step_fn, donate_argnums=donated),
            jax.jit(chunk_fn, static_argnames=("n_steps", "eos_id",
                                               "max_seq"),
                    donate_argnums=donated))


class DecodeEngine:
    """Throughput-oriented: continuous batching over a slotted cache.

    ``step()`` advances all slots by up to ``chunk_size`` tokens in ONE
    jitted device loop (current tokens, done-flags, and the emitted chunk
    all live on device; see ``registry.make_decode_chunk``), so the host is
    touched once per chunk. ``step_reference()`` keeps the one-token-per-
    host-round-trip path for A/B benchmarking and equivalence tests.

    With ``paged=True`` (pure-attention archs) the dense ``[max_slots,
    max_seq]`` cache is replaced by a page pool that stays int4-quantized
    at rest (``kv_resident="bf16"`` for ablation): admission reserves
    ``ceil((prompt + max_new) / page_size)`` pages per request instead of
    a worst-case ``max_seq`` column, aligned wires scatter into pages with
    no dequant round-trip, attention dequantizes in-kernel, and capacity
    is governed by the PAGE BUDGET — ``free_slots()`` truncates to what
    the pool can actually admit, which is what the gateway's dispatch and
    shedding read. Unsupported archs fall back to the dense path
    (``paged_fallback`` records why).

    On an accelerator a paged engine donates its cache to the decode
    step and chunk: the chunk's pool output aliases its input, so a step
    neither copies the pool nor holds two of it. The arrays passed in are
    dead afterwards; everything here reads ``self.cache`` afresh. On the
    CPU it does not donate: memory does not bound the pool there, and
    tests and fault injection keep a cache from before a step.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 8,
                 max_seq: int = 512, rt=None, eos_id: int = -1,
                 chunk_size: int = 8, paged: bool = False,
                 page_size: int = paged_fmt.DEFAULT_PAGE_SIZE,
                 num_pages: Optional[int] = None,
                 kv_resident: str = "int4", paged_backend: str = "auto",
                 prefix_sharing: bool = False):
        self.cfg = cfg
        self.params = params
        self.api = registry.build(cfg, rt=rt)
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.chunk_size = chunk_size
        supported = self.api.paged_decode_fns is not None
        self.paged = bool(paged) and supported
        self.paged_fallback = (
            None if (not paged or supported) else
            f"{cfg.name}: arch cannot page its decode cache (recurrent "
            f"state / SWA ring buffer / audio / softcap)")
        if self.paged:
            self.page_size = page_size
            self.table_w = paged_fmt.table_width(max_seq, page_size)
            if num_pages is None:
                # parity with the dense max_slots x max_seq budget (+ the
                # trash page); real deployments size this from HBM instead
                num_pages = max_slots * self.table_w + 1
            self.kv_resident = kv_resident
            if _sanitize_enabled():
                from repro.analysis.sanitizers import make_sanitized_pool
                self.pool = make_sanitized_pool(num_pages, page_size)
            else:
                self.pool = PagePool(num_pages, page_size)
            self.cache = paged_fmt.init_paged_cache(
                cfg, max_slots, max_seq, num_pages, page_size=page_size,
                resident=kv_resident)
            self._decode, self._chunk = paged_decode_jits(
                self.api, page_size, paged_backend,
                donate=jax.default_backend() != "cpu")
            self._slot_pages: Dict[int, List[int]] = {}
            self._need_sum = 0      # pages reserved across admissions
            self._need_n = 0
            self.zero_copy_inserts = 0
            self.reencoded_inserts = 0
            # prefix sharing: finished chains are donated to a radix index
            # and later requests decode straight off the shared pages
            self.prefix_cache = (PrefixCache(page_size) if prefix_sharing
                                 else None)
            self._pins: Dict[object, List[int]] = {}
            self.cow_copies = 0
            self.prefix_admits = 0
        else:
            init_fn = (registry.whisper.init_cache if cfg.family == "audio"
                       else registry.transformer.init_cache)
            self.cache = init_fn(cfg, max_slots, max_seq)
            self._decode = jax.jit(
                lambda p, c, b: self.api.decode(p, c, b))
            self._chunk = jax.jit(
                self.api.decode_chunk,
                static_argnames=("n_steps", "eos_id", "max_seq"))
        self.slots: List[Optional[GenRequest]] = [None] * max_slots
        self.cur_token = np.zeros((max_slots,), np.int32)
        # host-sync accounting (benchmarks read these)
        self.host_syncs = 0
        self.steps_run = 0
        # decode chunks run, and the page-table entries the paged kernel
        # read live vs. walked, summed over the chunks' steps
        self.decode_chunks = 0
        self.decode_live_pages = 0
        self.decode_grid_pages = 0

    # -- slot management ----------------------------------------------------

    def free_slots(self) -> List[int]:
        """Admissible slot indices. Paged engines truncate the raw free
        list to the PAGE BUDGET: free pages divided by the mean reserved
        pages per admitted request (worst-case table width before any
        observation) — the number the gateway's dispatch/shedding sees."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not self.paged or not free:
            return free
        est = (self._need_sum / self._need_n) if self._need_n \
            else float(self.table_w)
        # pages held ONLY by the prefix index are reclaimable on demand
        # (evicted inside _alloc_pages), so they count as capacity — this
        # is how the hit rate turns into extra concurrent-decode headroom
        reclaimable = 0
        if self.prefix_cache is not None:
            reclaimable = sum(1 for p in self.prefix_cache.page_set()
                              if self.pool.refcount(p) == 1)
        cap = int((self.pool.n_free + reclaimable) / max(est, 1.0))
        return free[:max(cap, 0)]

    def _alloc_pages(self, n: int, owner) -> Optional[List[int]]:
        """Pool alloc with eviction-retry: on failure, evict unshared
        prefix-cache entries (LRU) to cover the shortfall, then retry."""
        if n == 0:
            return []
        pages = self.pool.alloc(n, owner)
        if pages is None and self.prefix_cache is not None:
            self.prefix_cache.evict(self.pool, n - self.pool.n_free)
            pages = self.pool.alloc(n, owner)
        return pages

    def admit(self, batch: AdmissionBatch, *,
              backend: str = "auto") -> AdmissionBatch:
        """Unified admission: one FIFO pass over an :class:`AdmissionBatch`
        whose items carry a typed source (FRESH | CHUNKED | PREFIX_HIT |
        MIGRATED); returns the rejected tail as an ``AdmissionBatch``.

        Admission stops at the first item capacity cannot place. Dense:
        one request per free slot, all wires inserted in ONE batched
        dequant launch. Paged: ALL-OR-NOTHING per request on the page
        budget — ``ceil((prompt + max_new)/page_size)`` pages reserved up
        front so an admitted stream can never die of a mid-decode page
        fault; PREFIX_HIT items share their resident chain (COW-splitting
        the boundary page when the prompt ends mid-page) and wire items
        scatter in one ``insert_wires`` launch."""
        if not isinstance(batch, AdmissionBatch):
            raise TypeError(
                "admit() takes an AdmissionBatch — the positional "
                "admit(req, wire, first_token) shim and the "
                "admit_batch/admit_prefix/admit_migrated variants were "
                "deleted (wrap items in AdmissionItem/AdmissionBatch)")
        items = list(batch.items)
        if _sanitize_enabled() and self.paged:
            # a migrated wire re-encoding (instead of zero-copy page
            # scatter) means extract_slot_wire/insert_wires drifted apart
            from repro.analysis.sanitizers import check_wire_alignment
            for it in items:
                if it.source == ADMIT_MIGRATED:
                    check_wire_alignment(it.wire, self.cfg,
                                         context=f"admit MIGRATED "
                                                 f"rid={it.req.rid}")
        with tracing.span("admit",
                          lambda: {"rids": [it.req.rid for it in items]}):
            if self.paged:
                n = self._admit_paged(items, backend=backend)
            else:
                n = self._admit_dense(items, backend=backend)
        return AdmissionBatch(items[n:])

    def _admit_dense(self, items: List[AdmissionItem], *, backend) -> int:
        free = self.free_slots()
        placed = []
        for it in items:
            if not free or it.source == ADMIT_PREFIX_HIT:
                break       # page-handle admission needs the paged pool
            placed.append((it, free.pop(0)))
        if placed:
            self.cache = kv_transfer.insert_batch(
                self.cache, [(it.wire, slot) for it, slot in placed],
                backend=backend)
            for it, slot in placed:
                self.slots[slot] = it.req
                self.cur_token[slot] = it.token
                if it.source != ADMIT_MIGRATED:
                    it.req.out_tokens.append(it.token)
        return len(placed)

    def _admit_paged(self, items: List[AdmissionItem], *, backend) -> int:
        n_taken = 0
        placed = []                      # wire-carrying placements
        free = [i for i, s in enumerate(self.slots) if s is None]
        for it in items:
            if it.source == ADMIT_PREFIX_HIT:
                # full hit: chain is resident, prefill was skipped —
                # placed inline (page copies, no wire insert)
                if not self._admit_one_prefix(it.req, it.pages or [],
                                              it.token):
                    break
                free = [i for i, s in enumerate(self.slots) if s is None]
                n_taken += 1
                continue
            if not free:
                break
            req, wire = it.req, it.wire
            migrated = it.source == ADMIT_MIGRATED
            # partial prefix hit: the wire covers only the suffix; the
            # shared prefix chain is already resident — share it under
            # this slot and splice suffix pages after it
            prefix = (list(req.prefix_pages)
                      if (not migrated and req.prefix_pages) else [])
            budget = min(len(req.tokens) + req.max_new_tokens, self.max_seq)
            need = min(pages_needed(budget, self.page_size), self.table_w) \
                - len(prefix)
            need = max(need, pages_needed(wire.request_len, self.page_size))
            if len(prefix) + need > self.table_w:
                break                   # chain cannot fit the table row
            pages = self._alloc_pages(need, free[0])
            if pages is None:           # page budget exhausted: stop (FIFO)
                break
            if prefix:
                try:
                    self.pool.share(prefix, free[0])
                except ValueError:
                    # prefix chain vanished (pin lost); surface as reject
                    self.pool.free(pages, owner=free[0])
                    break
            slot = free.pop(0)
            placed.append((it, slot, pages, prefix))
            n_taken += 1
        if placed:
            self.cache, nz, nr = page_pool.insert_wires(
                self.cache, self.cfg,
                [(it.wire, s, p, pre) for it, s, p, pre in placed],
                backend=backend)
            self.zero_copy_inserts += nz
            self.reencoded_inserts += nr
            for it, slot, pages, prefix in placed:
                self.slots[slot] = it.req
                self._slot_pages[slot] = prefix + pages
                self.cur_token[slot] = it.token
                if it.source != ADMIT_MIGRATED:
                    it.req.out_tokens.append(it.token)
                # only freshly ALLOCATED pages count toward the per-request
                # page-need estimate: shared prefixes cost no free pages,
                # which is exactly the capacity gain free_slots() credits
                self._need_sum += len(pages)
                self._need_n += 1
        return n_taken

    # -- prefix sharing -----------------------------------------------------

    def prefix_match(self, tokens) -> Optional[PrefixMatch]:
        """Probe the radix index with a prompt (gateway dispatch)."""
        if not self.paged or self.prefix_cache is None:
            return None
        return self.prefix_cache.match([int(t) for t in tokens])

    def prefix_pin(self, pages: List[int], tag) -> bool:
        """Take a reference on matched pages under an in-flight tag so
        neither eviction nor a donor release can recycle them between
        match and admission. Idempotence is the caller's problem."""
        try:
            self.pool.share(pages, tag)
        except ValueError:
            return False
        self._pins[tag] = list(pages)
        return True

    def prefix_unpin(self, tag):
        pages = self._pins.pop(tag, None)
        if pages:
            self.pool.unshare(pages, tag)

    def extract_prefix(self, pages: List[int], length: int) -> KVWire:
        """Gather a (pinned) prefix chain into a wire for the suffix
        prefill — a pure page gather, no dequantization here."""
        return page_pool.extract_slot_wire(self.cache, self.cfg, length,
                                           pages)

    def _admit_one_prefix(self, req: GenRequest, pages: List[int],
                          next_token: int) -> bool:
        """Admit a FULL prefix hit: every prompt token's KV is already
        resident in ``pages`` and ``next_token`` is the known first
        output, so prefill is skipped entirely — zero transfer, zero
        dequant. The slot shares the chain; if decode's next append lands
        in a shared page (prompt ends mid-page), that single page is
        copy-on-write duplicated first."""
        if not self.paged:
            return False
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free:
            return False
        slot = free[0]
        ln = len(req.tokens)
        budget = min(ln + req.max_new_tokens, self.max_seq)
        need_total = min(pages_needed(budget, self.page_size), self.table_w)
        n_extra = max(need_total - len(pages), 0)
        # next append hits a shared page -> that page must be COW-split
        # (boundary arithmetic is the protocol contract the model checker
        # explores; see serving/protocol.py)
        cow_at = protocol.cow_boundary(ln, self.page_size, self.table_w)
        cow = protocol.cow_needed(ln, self.page_size, self.table_w,
                                  len(pages))
        alloced = self._alloc_pages(n_extra + int(cow), slot)
        if alloced is None:
            return False
        try:
            self.pool.share(pages, slot)
        except ValueError:
            if alloced:
                self.pool.free(alloced, owner=slot)
            return False
        chain = list(pages) + (alloced[:n_extra] if cow else alloced)
        if cow:
            repl = alloced[-1]
            self.cache = page_pool.copy_page(self.cache, chain[cow_at],
                                             repl)
            self.pool.unshare([chain[cow_at]], slot)
            chain[cow_at] = repl
            self.cow_copies += 1
        self.cache = page_pool.set_page_chain(self.cache, slot, chain, ln)
        self.slots[slot] = req
        self._slot_pages[slot] = chain
        self.cur_token[slot] = next_token
        req.out_tokens.append(next_token)
        self._need_sum += len(alloced)
        self._need_n += 1
        self.prefix_admits += 1
        return True

    def _retire_slot(self, slot: int, req: GenRequest, kv_len: int):
        """Release a finished slot's pages, first donating the chain to
        the prefix index — donated pages live on under the index's owner
        tag; the rest return to the free list. The donate-BEFORE-free
        ordering comes from ``protocol.retire_steps`` (the model checker
        explores the same sequence; reversing it shares pages that are
        already on the free list)."""
        donate = (self.prefix_cache is not None and req is not None
                  and kv_len > 0)
        chain = self._slot_pages.get(slot, []) if donate else []
        n_used = pages_needed(kv_len, self.page_size) if donate else 0
        donate = bool(donate and chain and n_used <= len(chain))
        for op in protocol.retire_steps(donate):
            if op == "donate":
                toks = [int(t) for t in req.tokens] + \
                    [int(t) for t in req.out_tokens]
                self.prefix_cache.insert(toks, kv_len, chain[:n_used],
                                         len(req.tokens), self.pool)
            elif op == "free":
                self._free_pages_of(slot)

    def clear_prefix(self) -> int:
        """Drop the radix index AND any in-flight pins (drain / phase
        flip): releases every cache-held page reference so a drained pool
        really is all-free. Gateway-side pin records become stale no-ops."""
        if not self.paged:
            return 0
        for tag in list(self._pins):
            self.prefix_unpin(tag)
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.clear(self.pool)

    # -- live migration (preemption drains) ---------------------------------

    def extract_resident(self, *, compress: bool = True,
                         backend: str = "auto"
                         ) -> List[Tuple[int, GenRequest, KVWire, int]]:
        """Snapshot every resident request as (slot, req, wire, cur_token)
        for migration to another decode replica.

        The wire covers exactly the tokens whose K/V is in the cache
        (prompt + generated-so-far); ``cur_token`` — the last emitted
        token, whose K/V is appended by the NEXT step — rides alongside so
        the destination resumes mid-stream without regenerating anything.
        Paged engines gather pages zero-dequant
        (:func:`~repro.serving.page_pool.extract_slot_wire`); dense ones
        go through the standard ``kv_transfer.extract`` path. Slots are
        NOT released — the caller frees them once the handoff commits."""
        out = []
        if self.active == 0:
            return out
        lengths = np.asarray(self.cache["lengths"])
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            ln = int(lengths[slot])
            if self.paged:
                wire = page_pool.extract_slot_wire(
                    self.cache, self.cfg, ln, self._slot_pages[slot])
            else:
                wire = kv_transfer.extract(self.cache, slot, ln,
                                           compress=compress,
                                           backend=backend)
            out.append((slot, req, wire, int(self.cur_token[slot])))
        return out

    def _free_pages_of(self, slot: int):
        pages = self._slot_pages.pop(slot, [])
        if pages:
            self.pool.free(pages, owner=slot)

    def release(self, slot: int) -> Optional[GenRequest]:
        """Free one slot (cancellation / failure recovery): clears the
        request, returns every page to the pool (paged), and zeroes the
        slot's cache length so a later admit starts from a clean masked
        extent."""
        req = self.slots[slot]
        self.slots[slot] = None
        if self.paged:
            self._free_pages_of(slot)
            self.cache = page_pool.release_slot(self.cache, slot)
        else:
            self.cache["lengths"] = self.cache["lengths"].at[slot].set(0)
        return req

    @property
    def active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def jit_cache_size(self) -> int:
        """Total compiled variants across the decode jits (the sanitizer's
        retrace monitor flags growth after warmup)."""
        n = 0
        for fn in (self._decode, self._chunk):
            sz = getattr(fn, "_cache_size", None)
            if callable(sz):
                n += sz()
        return n

    # -- stepping -----------------------------------------------------------

    def _host_state(self):
        active = np.array([s is not None for s in self.slots], bool)
        n_out = np.array([len(s.out_tokens) if s else 0 for s in self.slots],
                         np.int32)
        max_new = np.array([s.max_new_tokens if s else 0 for s in self.slots],
                           np.int32)
        return {"cur": jnp.asarray(self.cur_token),
                "active": jnp.asarray(active),
                "n_out": jnp.asarray(n_out),
                "max_new": jnp.asarray(max_new)}

    def step(self, n_steps: Optional[int] = None) -> List[GenRequest]:
        """Advance all active slots by up to ``chunk_size`` tokens; returns
        finished requests. ONE host synchronization for the whole chunk."""
        if self.active == 0:
            return []
        n = n_steps or self.chunk_size
        live = [r for r in self.slots if r is not None]
        pages = {}
        if self.paged:
            # pages holding the live slots' context at the chunk's start
            # (the last emitted token's K/V is written by the chunk), and
            # the pages each launch of the kernel copies: the first step
            # attends over that token too, and an idle slot (length 0)
            # over one key on the trash page
            ctx = [len(r.tokens) + len(r.out_tokens) - 1 for r in live]
            kv_len = [c + 1 for c in ctx] + [1] * (self.max_slots - len(live))
            pages = {"live_pages": sum(-(-c // self.page_size) for c in ctx),
                     "grid_pages": paged_attention.grid_pages(
                         kv_len, self.page_size)}
            self.decode_live_pages += pages["live_pages"] * n
            self.decode_grid_pages += pages["grid_pages"] * n
        self.decode_chunks += 1
        with tracing.span("decode_chunk", lambda: {
                "rids": [r.rid for r in live], "live_slots": len(live),
                "steps": n, **pages}):
            return self._step_chunk(n)

    def _step_chunk(self, n: int) -> List[GenRequest]:
        toks_d, valid_d, self.cache, st = self._chunk(
            self.params, self.cache, self._host_state(),
            n_steps=n, eos_id=self.eos_id, max_seq=self.max_seq)
        # the single device->host hop for this chunk
        with tracing.span("readback"):
            toks, valid, cur, still_active, lengths = jax.device_get(
                (toks_d, valid_d, st["cur"], st["active"],
                 self.cache["lengths"]))
        self.host_syncs += 1
        self.steps_run += n
        self.cur_token = np.array(cur)   # writable copy (admit mutates it)
        finished = []
        freed = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            req.out_tokens.extend(int(t) for t in toks[valid[:, i], i])
            if not still_active[i]:
                finished.append(req)
                self.slots[i] = None
                freed.append(i)
        if freed:
            with tracing.span("release",
                              lambda: {"rids": [r.rid for r in finished]}):
                self._release_finished(finished, freed, lengths)
        return finished

    def _release_finished(self, finished: List[GenRequest],
                          freed: List[int], lengths: np.ndarray):
        # release the slots' cache lengths like step_reference does — the
        # scan freezes lengths for inactive slots, so without this a
        # finished slot would keep its old extent until re-admission
        self.cache["lengths"] = \
            self.cache["lengths"].at[jnp.asarray(freed)].set(0)
        if self.paged:
            # finished chains are donated to the prefix index before the
            # slot's references go back to the pool; the table row points
            # back at the trash page
            for req, i in zip(finished, freed):
                self._retire_slot(i, req, int(lengths[i]))
            self.cache["page_table"] = \
                self.cache["page_table"].at[jnp.asarray(freed)].set(0)

    def step_reference(self) -> List[GenRequest]:
        """SEED PATH (kept for A/B benchmarks + equivalence tests): one
        decode step per call, one host sync + Python slot loop per token."""
        if self.active == 0:
            return []
        batch = {"tokens": jnp.asarray(self.cur_token[:, None])}
        logits, self.cache = self._decode(self.params, self.cache, batch)
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        self.host_syncs += 1
        self.steps_run += 1
        finished = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(nxt[i])
            req.out_tokens.append(tok)
            self.cur_token[i] = tok
            done = (len(req.out_tokens) >= req.max_new_tokens
                    or tok == self.eos_id
                    or int(self.cache["lengths"][i]) >= self.max_seq - 1)
            if done:
                finished.append(req)
                self.slots[i] = None
                kv_len = int(self.cache["lengths"][i])
                self.cache["lengths"] = \
                    self.cache["lengths"].at[i].set(0)
                if self.paged:
                    self._retire_slot(i, req, kv_len)
                    self.cache["page_table"] = \
                        self.cache["page_table"].at[i].set(0)
        return finished

    # -- paged accounting ---------------------------------------------------

    def page_stats(self) -> Optional[Dict[str, float]]:
        """Pool occupancy + internal fragmentation (reserved-but-unused
        token fraction; generated counts are host-visible, so mid-chunk
        tokens are slightly understated). None for dense engines."""
        if not self.paged:
            return None
        st = self.pool.stats()
        reserved = sum(len(p) for p in self._slot_pages.values()) \
            * self.page_size
        used = sum(len(r.tokens) + len(r.out_tokens)
                   for r in self.slots if r is not None)
        st["resident_tokens"] = used
        st["reserved_tokens"] = reserved
        st["internal_frag"] = (1.0 - used / reserved) if reserved else 0.0
        st["zero_copy_inserts"] = self.zero_copy_inserts
        st["reencoded_inserts"] = self.reencoded_inserts
        # pages the pool holds that nothing references — should be 0
        # always; a release path that skipped pool.free shows up here
        # (and trips the REPRO_SANITIZE drain audit). The prefix index
        # and in-flight pins are legitimate holders.
        referenced = {p for ps in self._slot_pages.values() for p in ps}
        if self.prefix_cache is not None:
            referenced |= self.prefix_cache.page_set()
        for pinned in self._pins.values():
            referenced.update(pinned)
        st["leaked_pages"] = sum(1 for p in self.pool.pages_in_use()
                                 if p not in referenced)
        st["cow_copies"] = self.cow_copies
        st["prefix_admits"] = self.prefix_admits
        st["decode_chunks"] = self.decode_chunks
        st["decode_live_pages"] = self.decode_live_pages
        st["decode_grid_pages"] = self.decode_grid_pages
        if self.prefix_cache is not None:
            for k, v in self.prefix_cache.stats().items():
                st[f"prefix_{k}"] = v
        return st


# -- phase-switchable replica -------------------------------------------------


class Replica:
    """One model replica that owns its parameters ONCE and can host either
    serving role (paper §3.4's core trick: a phase flip re-uses the
    resident, already-sharded weights — no reload, no restart).

    ``switch_phase()`` constructs (or re-activates) the engine for the
    other phase around the SAME parameter buffers. Engines are cached per
    phase, so a replica that flips back re-enters a warm jit cache. A
    decode replica must be drained (or its requests requeued by the
    gateway) before flipping — the slotted KV cache does not survive a
    role change. The page pool is likewise DECODE-phase-owned: it lives on
    the cached decode engine, so a drained flip leaves an all-free pool
    behind and a warm re-flip re-enters it without reallocation.
    """

    def __init__(self, cfg: ModelConfig, params, *,
                 phase: str = "prefill", max_seq: int = 512, rt=None,
                 prefill_kw: Optional[Dict] = None,
                 decode_kw: Optional[Dict] = None):
        if phase not in ("prefill", "decode"):
            raise ValueError(f"unknown phase {phase!r}")
        self.cfg = cfg
        self.params = params
        self._prefill_kw = {"max_seq": max_seq, "rt": rt,
                            **(prefill_kw or {})}
        self._decode_kw = {"max_seq": max_seq, "rt": rt,
                           **(decode_kw or {})}
        self._engines: Dict[str, object] = {}
        self.phase = ""
        self.switches = 0
        self.engine = None
        self._activate(phase)

    def _activate(self, phase: str):
        if phase not in self._engines:
            if phase == "prefill":
                self._engines[phase] = PrefillEngine(self.cfg, self.params,
                                                     **self._prefill_kw)
            else:
                self._engines[phase] = DecodeEngine(self.cfg, self.params,
                                                    **self._decode_kw)
        self.engine = self._engines[phase]
        self.phase = phase

    @property
    def drained(self) -> bool:
        """True when no request state would be lost by a phase flip."""
        return self.phase != "decode" or self.engine.active == 0

    def switch_phase(self, phase: Optional[str] = None):
        """Flip to ``phase`` (default: the other one) around the resident
        parameter buffers. Raises if an undrained decode engine would lose
        in-flight requests — the caller (gateway) drains or requeues them
        first. Returns the now-active engine."""
        target = phase or ("decode" if self.phase == "prefill"
                           else "prefill")
        if target not in ("prefill", "decode"):
            raise ValueError(f"unknown phase {target!r}")
        if target == self.phase:
            return self.engine
        if not self.drained:
            raise RuntimeError(
                f"cannot flip an undrained {self.phase} replica "
                f"({self.engine.active} request(s) resident): drain or "
                f"requeue them first")
        if self.phase == "decode" and hasattr(self.engine, "clear_prefix"):
            # shared prefixes don't survive a role change: release the
            # index's page references so the pool is left all-free
            self.engine.clear_prefix()
        self._activate(target)
        self.switches += 1
        return self.engine
