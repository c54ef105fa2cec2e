"""Mixture-of-Experts with capacity-based token dispatch.

Two execution paths sharing one routing implementation:

* ``moe_apply`` — plain jnp; under pjit the expert dimension of the weights is
  sharded over the ``model`` axis and XLA SPMD inserts the all-to-alls. This
  is the path lowered by the dry-run (and the smoke path on 1 CPU device).
* ``moe_apply_shard_map`` — explicit expert parallelism: tokens are
  sequence-sharded, dispatched into per-expert capacity buffers, exchanged
  with ``lax.all_to_all`` over the expert (``model``) mesh axis, FFN'd by the
  expert owners, and returned. Used by the optimized (beyond-paper) configs;
  validated against ``moe_apply`` on a multi-device CPU mesh in tests.

Routing follows GShard/Switch: top-k gates, renormalized, position-in-expert
via per-choice cumulative sums, tokens beyond ``capacity`` dropped (residual
passes through untouched — standard behaviour).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def moe_init(cfg, key):
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.moe_num_experts
    gated = cfg.mlp_type in ("silu", "geglu")
    ks = jax.random.split(key, 4)
    wi_dim = 2 * f if gated else f
    p = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32)
                   / math.sqrt(d)).astype(jnp.float32),
        "wi": (jax.random.normal(ks[1], (E, d, wi_dim), jnp.float32)
               / math.sqrt(d)).astype(jnp.bfloat16),
        "wo": (jax.random.normal(ks[2], (E, f, d), jnp.float32)
               / math.sqrt(f)).astype(jnp.bfloat16),
    }
    if cfg.moe_shared_expert:
        p["shared"] = layers.mlp_init(cfg, ks[3], d_ff=f)
    return p


# ---------------------------------------------------------------------------
# Routing (shared)
# ---------------------------------------------------------------------------


def route(cfg, router_w, x2d, capacity: int):
    """x2d: (T, d) tokens. Returns (expert_idx, slot_pos, keep, gates): (T,k).

    Position-in-expert computed choice-major (all first choices get slots
    before any second choice) so higher-priority routes are dropped last.
    """
    T = x2d.shape[0]
    k, E = cfg.moe_top_k, cfg.moe_num_experts
    logits = (x2d.astype(jnp.float32) @ router_w)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, k)  # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    pos_list, base = [], jnp.zeros((E,), jnp.int32)
    for c in range(k):  # k is small & static
        oh = jax.nn.one_hot(idx[:, c], E, dtype=jnp.int32)  # (T, E)
        pos_c = (jnp.cumsum(oh, axis=0) - 1) + base[None, :]
        pos_list.append((pos_c * oh).sum(-1))
        base = base + oh.sum(0)
    pos = jnp.stack(pos_list, axis=1)  # (T, k)
    keep = pos < capacity
    # router z-loss / aux load-balance loss (Switch) for training
    me = probs.mean(0)                       # (E,) mean gate prob
    ce = jnp.zeros((E,), jnp.float32)
    for c in range(k):
        ce = ce + jax.nn.one_hot(idx[:, c], E, dtype=jnp.float32).mean(0)
    aux_loss = E * jnp.sum(me * ce / k)
    return idx, pos, keep, gates.astype(jnp.float32), aux_loss


def _expert_ffn(cfg, wi, wo, buf):
    """buf: (E, C, d) -> (E, C, d) via per-expert gated FFN."""
    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    if cfg.mlp_type in ("silu", "geglu"):
        g, u = jnp.split(h, 2, axis=-1)
        act = jax.nn.silu(g) if cfg.mlp_type == "silu" else jax.nn.gelu(g)
        h = act * u
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, wo)


# ---------------------------------------------------------------------------
# Path 1: plain jnp (XLA SPMD handles expert sharding)
# ---------------------------------------------------------------------------


def moe_apply(cfg, p, x, *, capacity_factor=None):
    """x: (B, S, d). Returns (y, aux_loss)."""
    B, S, d = x.shape
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    cf = capacity_factor or cfg.moe_capacity_factor
    T = B * S
    # floor at min(T, 32): decode-sized token counts must not drop on expert
    # collisions (a 2-token step would otherwise get capacity 1)
    capacity = max(min(T, 32), int(math.ceil(T * k * cf / E)))
    x2d = x.reshape(T, d)
    idx, pos, keep, gates, aux = route(cfg, p["router"], x2d, capacity)

    buf = jnp.zeros((E, capacity, d), x.dtype)
    tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k)).reshape(-1)
    ef = idx.reshape(-1)
    pf = jnp.clip(pos.reshape(-1), 0, capacity - 1)
    kf = keep.reshape(-1)
    buf = buf.at[ef, pf].add(x2d[tok] * kf[:, None].astype(x.dtype),
                             mode="drop")
    y_buf = _expert_ffn(cfg, p["wi"], p["wo"], buf)  # (E, C, d)
    y_tok = y_buf[ef, pf] * (kf[:, None] * gates.reshape(-1)[:, None]
                             ).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok].add(y_tok)
    if cfg.moe_shared_expert:
        y = y + layers.mlp_apply(cfg, p["shared"], x2d)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Path 2: explicit expert-parallel shard_map
# ---------------------------------------------------------------------------


def moe_apply_shard_map(cfg, p, x, *, mesh, expert_axis="model",
                        data_axis="data", capacity_factor=None):
    """Expert parallelism with explicit a2a. x: (B, S, d) global.

    Tokens are sharded (batch over ``data_axis``, sequence over
    ``expert_axis``); expert weights are sharded over ``expert_axis``.
    Each device routes its local tokens, builds an (E, C_loc, d) buffer,
    all_to_all's it so device j receives the slots of its own E/ep experts
    from every peer in its data row, runs the FFN, and reverses the exchange.
    """
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape[expert_axis]
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    assert E % ep == 0, (E, ep)
    B, S, d = x.shape
    cf = capacity_factor or cfg.moe_capacity_factor
    seq_shard = S % ep == 0 and S >= ep

    data_axes = ("pod", data_axis) if "pod" in mesh.shape else (data_axis,)
    n_data = math.prod(mesh.shape[a] for a in data_axes)
    batch_shard = B % n_data == 0
    data_spec = data_axes if batch_shard else None
    b_loc = B // n_data if batch_shard else B

    if seq_shard:
        x_spec = P(data_spec, expert_axis, None)
        T_loc = b_loc * (S // ep)
    else:  # decode / tiny-S: tokens replicated over expert axis
        x_spec = P(data_spec, None, None)
        T_loc = b_loc * S
    capacity = max(min(T_loc, 32), int(math.ceil(T_loc * k * cf / E)))

    w_specs = {"router": P(None, None), "wi": P(expert_axis, None, None),
               "wo": P(expert_axis, None, None)}
    if cfg.moe_shared_expert:
        w_specs["shared"] = {"wi": {"w": P(None, expert_axis)},
                             "wo": {"w": P(expert_axis, None)}}

    def local_fn(router_w, wi, wo, xl):
        t = xl.shape[0] * xl.shape[1]
        x2d = xl.reshape(t, d)
        idx, pos, keep, gates, aux = route(cfg, router_w, x2d, capacity)
        tok = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(-1)
        ef, pf = idx.reshape(-1), jnp.clip(pos.reshape(-1), 0, capacity - 1)
        kf = keep.reshape(-1)

        if seq_shard:
            buf = jnp.zeros((E, capacity, d), xl.dtype)
            buf = buf.at[ef, pf].add(
                x2d[tok] * kf[:, None].astype(xl.dtype), mode="drop")
            # (E, C, d) -> (E/ep, ep*C, d): expert owners gather their slots
            # (peer-major along the capacity axis).
            buf = lax.all_to_all(buf, expert_axis, split_axis=0,
                                 concat_axis=1, tiled=True)
            y_buf = _expert_ffn(cfg, wi, wo, buf)
            # (E/ep, ep*C, d) -> (E, C, d): results return to token owners.
            y_buf = lax.all_to_all(y_buf, expert_axis, split_axis=1,
                                   concat_axis=0, tiled=True)
            y_tok = y_buf[ef, pf]
            y_tok = y_tok * (kf[:, None] * gates.reshape(-1)[:, None]
                             ).astype(xl.dtype)
            y = jnp.zeros((t, d), xl.dtype).at[tok].add(y_tok)
        else:
            # replicated tokens: each device serves only its local experts,
            # combine with a psum over the expert axis.
            eid = lax.axis_index(expert_axis)
            lo = eid * (E // ep)
            local = (ef >= lo) & (ef < lo + (E // ep))
            buf = jnp.zeros((E // ep, capacity, d), xl.dtype)
            buf = buf.at[jnp.where(local, ef - lo, 0), pf].add(
                x2d[tok] * (kf & local)[:, None].astype(xl.dtype), mode="drop")
            y_buf = _expert_ffn(cfg, wi, wo, buf)
            y_tok = y_buf[jnp.where(local, ef - lo, 0), pf]
            y_tok = y_tok * ((kf & local)[:, None]
                             * gates.reshape(-1)[:, None]).astype(xl.dtype)
            y = jnp.zeros((t, d), xl.dtype).at[tok].add(y_tok)
            y = lax.psum(y, expert_axis)
        aux = lax.pmean(aux, tuple(mesh.shape.keys()))
        return y.reshape(xl.shape), aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(w_specs["router"], w_specs["wi"], w_specs["wo"], x_spec),
        out_specs=(x_spec, P()), check_vma=False)
    y, aux = fn(p["router"], p["wi"], p["wo"], x)
    if cfg.moe_shared_expert:
        y = y + layers.mlp_apply(cfg, p["shared"], x)
    return y, aux
