"""Pipeline parallelism over a mesh axis via shard_map + collective_permute.

The paper's deployment plans combine TP x PP per replica; on TPU pods the
natural PP cut is the inter-pod ("pod") axis — only activations cross the
DCN, matching the paper's heuristic that slow links carry pipeline (not
tensor) traffic.

GPipe loop schedule: rank 0 injects one microbatch per step, activations
hop rank->rank+1 with collective_permute, rank S-1 collects outputs;
n_steps = M + S - 1, bubble fraction (S-1)/(M+S-1). Stages run "garbage"
during fill/drain (masked on collection) — the standard SPMD formulation.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_apply(fn: Callable, params_stacked, x, *, mesh,
                   axis: str = "pod", n_microbatch: int = None):
    """Run ``fn(stage_params, microbatch) -> microbatch`` as a pipeline.

    params_stacked: pytree with a leading stage axis (== mesh.shape[axis]),
    sharded one-stage-per-rank along ``axis``. x: (B, ...) global batch
    (replicated across ``axis``; microbatches enter at rank 0).
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    M = n_microbatch or S
    assert B % M == 0, (B, M)

    def local(params_local, x_full):
        p = jax.tree.map(lambda a: a[0], params_local)
        rank = lax.axis_index(axis)
        mb = x_full.reshape((M, B // M) + x_full.shape[1:])
        n_steps = M + S - 1
        buf = jnp.zeros_like(mb[0])
        outbuf = jnp.zeros_like(mb)

        def step(carry, i):
            buf, outbuf = carry
            inject = mb[jnp.clip(i, 0, M - 1)]
            cur = jnp.where((rank == 0) & (i < M), inject, buf)
            out = fn(p, cur)
            idx = i - (S - 1)
            upd = lax.dynamic_update_index_in_dim(
                outbuf, out, jnp.clip(idx, 0, M - 1), 0)
            outbuf = jnp.where((rank == S - 1) & (idx >= 0), upd, outbuf)
            nxt = lax.ppermute(out, axis,
                               [(j, j + 1) for j in range(S - 1)])
            return (buf if S == 1 else nxt, outbuf), None

        (_, outbuf), _ = lax.scan(step, (buf, outbuf), jnp.arange(n_steps))
        # results live on the last rank; replicate via masked psum
        outbuf = lax.psum(
            jnp.where(rank == S - 1, outbuf, jnp.zeros_like(outbuf)), axis)
        return outbuf.reshape((B,) + x_full.shape[1:])

    spec_p = jax.tree.map(lambda _: P(axis), params_stacked)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(spec_p, P()),
                         out_specs=P(), check_vma=False)(params_stacked, x)


def pipeline_stage_specs(mesh, params_stacked, axis: str = "pod"):
    """NamedSharding specs placing stage i of the stacked params on rank i."""
    from jax.sharding import NamedSharding
    return jax.tree.map(
        lambda a: NamedSharding(mesh, P(*((axis,) + (None,) * (a.ndim - 1)))),
        params_stacked)
