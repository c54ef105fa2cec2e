"""The deployment a configuration file names, built from the program's
public constructors, with the benchmark's host spans around the replica
seams the ``Gateway`` accepts.

One ``PrefillEngine`` and one paged int4 ``DecodeEngine`` share one set of
weights on one chip, joined by the ``InProcessTransport`` wire with
``compress=True``: the phase split colocated on one device.

Host spans (``jax.profiler.TraceAnnotation``; free when no trace runs):
``bench.prefill``, ``bench.admit``, ``bench.decode_step``,
``bench.transfer``, and, from the loop (``bench/loop.py``), ``bench.pump``,
``bench.idle``.
The decode seam also logs each chunk's live slots and context lengths,
which the decode metrics turn into FLOPs and bytes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.engine import (ADMIT_FRESH, AdmissionBatch, AdmissionItem,
                                  DecodeEngine, GenRequest, PrefillEngine)
from repro.serving.gateway import (Gateway, LocalDecodeClient,
                                   LocalPrefillClient, SchedulerConfig)
from repro.serving.transport import InProcessTransport


@dataclass
class ChunkLog:
    """One decode chunk: host times, the context length of each live slot
    before the chunk and the tokens it emitted in it."""
    t0: float
    t1: float
    ctx: List[int]
    emitted: List[int]


@dataclass
class PrefillLog:
    t0: float
    t1: float
    true_lens: List[int]
    buckets: List[int]


@dataclass
class SeamLog:
    chunks: List[ChunkLog] = field(default_factory=list)
    prefills: List[PrefillLog] = field(default_factory=list)


def _bucket(engine: PrefillEngine, n: int) -> int:
    return min(max(1 << max((n - 1).bit_length(), 0), engine.min_bucket),
               engine.max_seq)


class SpannedPrefill(LocalPrefillClient):
    def __init__(self, engine: PrefillEngine, log: SeamLog, clock):
        super().__init__(engine)
        self.log = log
        self.clock = clock

    def prefill(self, reqs, *, compress, backend):
        t0 = self.clock()
        with TraceAnnotation("bench.prefill"):
            out = super().prefill(reqs, compress=compress, backend=backend)
        self.log.prefills.append(PrefillLog(
            t0, self.clock(), [len(r.tokens) for r in reqs],
            [_bucket(self.engine, len(r.tokens)) for r in reqs]))
        return out


class SpannedDecode(LocalDecodeClient):
    def __init__(self, engine: DecodeEngine, log: SeamLog, clock):
        super().__init__(engine)
        self.log = log
        self.clock = clock

    def admit(self, batch, *, backend):
        with TraceAnnotation("bench.admit"):
            return super().admit(batch, backend=backend)

    def step(self, n_steps=None):
        live = [r for r in self.engine.slots if r is not None]
        if not live:
            return super().step(n_steps)
        ctx = [len(r.tokens) + len(r.out_tokens) - 1 for r in live]
        before = [len(r.out_tokens) for r in live]
        t0 = self.clock()
        with TraceAnnotation("bench.decode_step"):
            out = super().step(n_steps)
        self.log.chunks.append(ChunkLog(
            t0, self.clock(), ctx,
            [len(r.out_tokens) - b for r, b in zip(live, before)]))
        return out


class SpannedTransport(InProcessTransport):
    def send(self, wire, src_replica, dst_replica, *, now=None):
        with TraceAnnotation("bench.transfer"):
            return super().send(wire, src_replica, dst_replica, now=now)


@dataclass
class Deployment:
    gateway: Gateway
    prefill: PrefillEngine
    decode: DecodeEngine
    log: SeamLog
    clock: object


def build(mcfg, params, engine: dict, clock=time.perf_counter) -> Deployment:
    prefill = PrefillEngine(mcfg, params, max_seq=engine["prefill_max_seq"],
                            max_batch=engine["max_prefill_batch"])
    decode = DecodeEngine(mcfg, params, max_slots=engine["max_slots"],
                          max_seq=engine["decode_max_seq"],
                          chunk_size=engine["decode_chunk_steps"],
                          paged=True, page_size=engine["page_size"],
                          num_pages=engine["num_pages"], kv_resident="int4",
                          prefix_sharing=False)
    if not decode.paged:
        raise RuntimeError(f"decode engine is not paged: "
                           f"{decode.paged_fallback}")
    log = SeamLog()
    gw = Gateway([SpannedPrefill(prefill, log, clock)],
                 [SpannedDecode(decode, log, clock)],
                 transport=SpannedTransport(clock=clock),
                 scheduler=SchedulerConfig(
                     max_prefill_batch=engine["max_prefill_batch"],
                     prefill_chunk_tokens=0),
                 compress=True, backend="auto", clock=clock,
                 heartbeat_timeout=engine["heartbeat_timeout_s"])
    return Deployment(gw, prefill, decode, log, clock)


def warm_up(dep: Deployment, lengths: List[int], vocab: int,
            rng: np.random.Generator) -> dict:
    """Compile what the cell's traffic will run, through the same client
    seams: every distinct prompt length once through prefill and
    admission (the wire and page-insert programs depend on the length);
    ``g`` prompts of one length bucket in one prefill call for every ``g``
    up to the prefill batch (the batch's programs depend on ``g``); one
    request through a decode chunk; then the scatters that release ``k``
    slots finished in one chunk (``DecodeEngine.step``), for every ``k``
    up to the slot count, on the cache's own arrays with the results
    dropped: those programs depend on ``k``, and a decode chunk a ``k``
    would cost a chunk's time each."""
    gw = dep.gateway
    pre, dec = gw.pre[0].client, gw.dec[0].client
    eng = dep.decode

    def serve_once(ns):
        reqs = [GenRequest(-1, rng.integers(1, vocab, n).astype(np.int32), 2)
                for n in ns]
        out = pre.prefill(reqs, compress=gw.compress, backend=gw.backend)
        if dec.admit(AdmissionBatch([AdmissionItem(r, first, ADMIT_FRESH,
                                                   wire=wire)
                                     for r, wire, first in out]),
                     backend=gw.backend):
            raise RuntimeError(f"warm-up requests of {ns} tokens rejected")
        for r, _, _ in out:
            dec.release(r)

    for n in lengths:
        serve_once([n])
    by_bucket = {}
    for n in lengths:
        by_bucket.setdefault(_bucket(dep.prefill, n), []).append(n)
    for ns in by_bucket.values():
        for g in range(2, dep.prefill.max_batch + 1):
            serve_once([ns[i % len(ns)] for i in range(g)])
    n = min(lengths)
    req = GenRequest(-1, rng.integers(1, vocab, n).astype(np.int32), 1)
    (_, wire, first), = pre.prefill([req], compress=gw.compress,
                                    backend=gw.backend)
    if dec.admit(AdmissionBatch([AdmissionItem(req, first, ADMIT_FRESH,
                                               wire=wire)]),
                 backend=gw.backend):
        raise RuntimeError("warm-up could not admit a request")
    while eng.active:
        dec.step()
    for k in range(2, eng.max_slots + 1):
        freed = jnp.asarray(list(range(k)))
        for name in ("lengths", "page_table"):
            eng.cache[name].at[freed].set(0).block_until_ready()
    dep.log.chunks.clear()
    dep.log.prefills.clear()
    gw.heartbeat_all()
    return {"lengths": len(lengths), "buckets": len(by_bucket),
            "release_widths": eng.max_slots}
