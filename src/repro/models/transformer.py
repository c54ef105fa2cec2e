"""Unified decoder-only transformer covering dense / MoE / VLM / hybrid / SSM
families, with scan-over-superblocks and a slotted state cache.

Layer structure is periodic (period = lcm of the interleave frequencies), so
the layer stack is a ``lax.scan`` over ``num_layers // period`` superblocks;
each superblock applies ``period`` slots with distinct param groups. This
keeps the HLO size O(period) regardless of depth — essential for compiling
the 94-layer MoE on a 512-device mesh.

Three entry points per model:
  * ``forward``  — full-sequence hidden states (training / loss)
  * ``prefill``  — forward + build the decode cache, return last-token logits
  * ``decode``   — one token against the cache (per-sequence positions)
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import constrain
from repro.models import layers, mamba, moe, xlstm
from repro.models.layers import dense, dense_init, norm_apply, norm_init


# ---------------------------------------------------------------------------
# Periodic structure
# ---------------------------------------------------------------------------


def _lcm(a, b):
    return a * b // math.gcd(a, b)


def period_of(cfg) -> int:
    p = 1
    if cfg.family == "hybrid" and cfg.attn_period:
        p = _lcm(p, cfg.attn_period)
    if cfg.moe_num_experts and cfg.moe_every > 1:
        p = _lcm(p, cfg.moe_every)
    if cfg.family == "ssm" and cfg.xlstm_slstm_every:
        p = _lcm(p, cfg.xlstm_slstm_every)
    if cfg.num_layers % p != 0:
        p = cfg.num_layers  # irregular: unroll everything
    return p


def slot_kinds(cfg):
    """Kinds of the first `period` layers (they repeat)."""
    kinds = cfg.layer_kinds()
    p = period_of(cfg)
    for i in range(p, cfg.num_layers):
        assert kinds[i] == kinds[i % p], (i, kinds[i], kinds[i % p])
    return kinds[:p]


# ---------------------------------------------------------------------------
# Per-slot block init/apply
# ---------------------------------------------------------------------------


def _block_init(cfg, kind: str, key):
    mix, ffn = kind.split("+")
    ks = jax.random.split(key, 4)
    p = {"norm1": norm_init(cfg, cfg.d_model)}
    if mix == "attn":
        p["attn"] = layers.attn_init(cfg, ks[0])
    elif mix == "mamba":
        p["mamba"] = mamba.mamba_init(cfg, ks[0])
    elif mix == "mlstm":
        p["mlstm"] = xlstm.mlstm_init(cfg, ks[0])
    elif mix == "slstm":
        p["slstm"] = xlstm.slstm_init(cfg, ks[0])
    if ffn == "moe":
        p["moe"] = moe.moe_init(cfg, ks[1])
        p["norm2"] = norm_init(cfg, cfg.d_model)
    elif ffn == "mlp":
        p["mlp"] = layers.mlp_init(cfg, ks[1])
        if not cfg.parallel_block:
            p["norm2"] = norm_init(cfg, cfg.d_model)
    return p


def _mix_cache_init(cfg, kind: str, batch: int, max_seq: int, dtype):
    """Abstract-safe cache slot for one layer of this kind."""
    mix = kind.split("+")[0]
    if mix == "attn":
        # sliding-window archs only ever need `window` cache slots
        s = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
        if cfg.decode_cache_layout == "hkv_s":
            shape = (batch, cfg.num_kv_heads, s, cfg.head_dim)
        else:
            shape = (batch, s, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if mix == "mamba":
        cs, ss = mamba.mamba_state_init(cfg, batch, dtype)
        return {"conv": cs, "ssm": ss}
    if mix == "mlstm":
        C, n, m = xlstm.mlstm_state_init(cfg, batch)
        conv = jnp.zeros((batch, 3, 2 * cfg.d_model), dtype)
        return {"C": C, "n": n, "m": m, "conv": conv}
    if mix == "slstm":
        c, n, h, m = xlstm.slstm_state_init(cfg, batch)
        return {"c": c, "n": n, "h": h, "m": m}
    raise ValueError(kind)


def _apply_ffn(cfg, p, kind, x, rt):
    """FFN half of a block. Returns (delta, aux_loss)."""
    ffn = kind.split("+")[1]
    if ffn == "none":
        return jnp.zeros_like(x), 0.0
    h = norm_apply(cfg, p.get("norm2", p["norm1"]), x)
    if ffn == "moe":
        if rt is not None and rt.moe_shard_map and rt.mesh is not None:
            return moe.moe_apply_shard_map(cfg, p["moe"], h, mesh=rt.mesh)
        return moe.moe_apply(cfg, p["moe"], h)
    return layers.mlp_apply(cfg, p["mlp"], h), 0.0


def _block_apply(cfg, p, kind, x, *, positions, mode, cache_slot, q_offset,
                 kv_len, window, rt):
    """Apply one block. Returns (x, new_cache_slot, aux_loss).

    mode: "full" (train/prefill over a whole sequence; new KV returned for
    cache construction) or "step" (decode: read+update cache). In "full"
    mode device work sits under named scopes (operation metadata only):
    ``qkv`` (the norm and the projections), ``attn`` (attention and its
    output projection) and ``ffn`` (the MLP, the parallel block's too).
    """
    mix = kind.split("+")[0]
    with jax.named_scope("qkv"):
        h = norm_apply(cfg, p["norm1"], x)
    aux = 0.0
    new_slot = cache_slot

    if mix == "attn":
        if mode == "full":
            with jax.named_scope("qkv"):
                q, k_new, v_new = layers.attn_qkv(cfg, p["attn"], h,
                                                  positions)
            with jax.named_scope("attn"):
                out = layers.attn_out(cfg, p["attn"], q, k_new, v_new,
                                      window=window, kv_len=kv_len,
                                      q_offset=q_offset)
            new_slot = (k_new, v_new)
        else:  # decode step against cache
            K, V = cache_slot["k"], cache_slot["v"]
            s_cache = K.shape[1]
            q, k, v = layers.attn_qkv(cfg, p["attn"], h, positions)
            if cfg.sliding_window and s_cache == cfg.sliding_window:
                # ring-buffer write for windowed cache
                w_pos = positions[:, 0] % s_cache
            else:
                w_pos = positions[:, 0]
            bidx = jnp.arange(K.shape[0])
            K = K.at[bidx, w_pos].set(k[:, 0])
            V = V.at[bidx, w_pos].set(v[:, 0])
            if cfg.sliding_window and s_cache == cfg.sliding_window:
                klen = jnp.minimum(positions[:, 0] + 1, s_cache)
                o = layers.attention(q, K, V, causal=False, window=0,
                                     kv_len=klen,
                                     softcap=cfg.attn_logit_softcap,
                                     chunk_q=cfg.attn_chunk_q,
                                     chunk_kv=cfg.attn_chunk_kv)
            else:
                o = layers.attention(q, K, V, causal=True, window=window,
                                     kv_len=positions[:, 0] + 1,
                                     q_offset=positions[:, 0],
                                     softcap=cfg.attn_logit_softcap,
                                     chunk_q=cfg.attn_chunk_q,
                                     chunk_kv=cfg.attn_chunk_kv)
            B = x.shape[0]
            out = dense(p["attn"]["wo"], o.reshape(B, 1, cfg.q_dim))
            new_slot = {"k": K, "v": V}
    elif mix == "mamba":
        if mode == "full":
            out, (conv_s, ssm_s) = mamba.mamba_apply(cfg, p["mamba"], h)
            new_slot = {"conv": conv_s, "ssm": ssm_s}
        else:
            out, (conv_s, ssm_s) = mamba.mamba_decode_step(
                cfg, p["mamba"], h, (cache_slot["conv"], cache_slot["ssm"]))
            new_slot = {"conv": conv_s, "ssm": ssm_s}
    elif mix == "mlstm":
        if mode == "full":
            out, (st, conv_s) = xlstm.mlstm_apply(cfg, p["mlstm"], h)
            new_slot = {"C": st[0], "n": st[1], "m": st[2], "conv": conv_s}
        else:
            out, st, conv_s = xlstm.mlstm_decode_step(
                cfg, p["mlstm"], h,
                (cache_slot["C"], cache_slot["n"], cache_slot["m"]),
                cache_slot["conv"])
            new_slot = {"C": st[0], "n": st[1], "m": st[2], "conv": conv_s}
    elif mix == "slstm":
        st = (None if mode == "full" else
              (cache_slot["c"], cache_slot["n"], cache_slot["h"],
               cache_slot["m"]))
        out, st = xlstm.slstm_apply(cfg, p["slstm"], h, state=st)
        new_slot = {"c": st[0], "n": st[1], "h": st[2], "m": st[3]}
    else:
        raise ValueError(kind)

    out = constrain(out, "batch", "seq", None)
    if cfg.parallel_block and "mlp" in p:
        # parallel residual: x + attn(n(x)) + mlp(n(x)) (single shared norm)
        with jax.named_scope("ffn"):
            out = out + layers.mlp_apply(cfg, p["mlp"], h)
        x = x + out
    else:
        x = x + out
        if kind.split("+")[1] != "none" and not (
                cfg.parallel_block and "mlp" in p):
            with jax.named_scope("ffn"):
                delta, aux = _apply_ffn(cfg, p, kind, x, rt)
            x = x + delta
    x = constrain(x, "batch", "seq", None)
    return x, new_slot, aux


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class Runtime:
    """Execution context: mesh + feature flags (plumbed, not global)."""

    def __init__(self, mesh=None, moe_shard_map=False, inplace_decode=False):
        self.mesh = mesh
        self.moe_shard_map = moe_shard_map
        self.inplace_decode = inplace_decode


def init_params(cfg, key):
    kinds = slot_kinds(cfg)
    p_blocks = {}
    n_super = cfg.num_layers // len(kinds)
    key, *slot_keys = jax.random.split(key, len(kinds) + 1)
    for s, kind in enumerate(kinds):
        sub = jax.random.split(slot_keys[s], n_super)
        stacked = jax.vmap(lambda k: _block_init(cfg, kind, k))(sub)
        p_blocks[f"slot{s}"] = stacked
    key, k_embed, k_unembed, k_proj = jax.random.split(key, 4)
    params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model),
                                    jnp.float32) * 0.02).astype(jnp.bfloat16),
        "blocks": p_blocks,
        "final_norm": norm_init(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (
            jax.random.normal(k_unembed, (cfg.d_model, cfg.vocab_size),
                              jnp.float32) / math.sqrt(cfg.d_model)
        ).astype(jnp.bfloat16)
    if cfg.family == "vlm":
        params["mm_projector"] = dense_init(k_proj, cfg.d_model, cfg.d_model)
    return params


def unembed_matrix(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["unembed"]


def head_logits(cfg, params, h):
    """f32 logits of final-normed hidden states ``h``: the output head,
    times ``cfg.logit_scale`` where that is not 1 (a scale of 1 adds no
    operation to the program)."""
    logits = (h @ unembed_matrix(cfg, params)).astype(jnp.float32)
    if cfg.logit_scale != 1.0:
        logits = logits * cfg.logit_scale
    return logits


def _embed_inputs(cfg, params, tokens, patch_embeds=None):
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.family == "vlm" and patch_embeds is not None:
        pe = dense(params["mm_projector"], patch_embeds.astype(x.dtype))
        x = jnp.concatenate([pe, x], axis=1)
    return x


def _stack_scan(cfg, params, x, body, *, remat=None):
    """Scan over superblocks; body(carry_x, slot_params_for_one_super)
    returns (x, per_super_outputs)."""
    remat = cfg.remat if remat is None else remat
    kinds = slot_kinds(cfg)
    n_super = cfg.num_layers // len(kinds)
    blocks = params["blocks"]
    fn = body
    if remat:
        fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.scan_layers and n_super > 1:
        x, ys = lax.scan(fn, x, blocks)
        return x, ys
    # unrolled
    ys = []
    for i in range(n_super):
        blk_i = jax.tree.map(lambda a: a[i], blocks)
        x, y = fn(x, blk_i)
        ys.append(y)
    ys = jax.tree.map(lambda *a: jnp.stack(a), *ys) if ys else None
    return x, ys


def forward(cfg, params, tokens, *, patch_embeds=None, rt=None,
            collect_cache=False, window=None):
    """Full-sequence forward. Returns (hidden, stacked_new_cache, aux_loss).
    The final norm sits under the named scope ``head``."""
    kinds = slot_kinds(cfg)
    x = _embed_inputs(cfg, params, tokens, patch_embeds)
    x = constrain(x, "batch", "seq", None)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.rope_theta <= 0 and cfg.family in ("dense", "vlm", "moe"):
        pass  # NoPE
    aux_total = jnp.float32(0.0)

    def body(carry, blk):
        x = carry
        aux_sum = jnp.float32(0.0)
        slots_out = {}
        for s, kind in enumerate(kinds):
            x, new_slot, aux = _block_apply(
                cfg, blk[f"slot{s}"], kind, x, positions=positions,
                mode="full", cache_slot=None, q_offset=None, kv_len=None,
                window=window, rt=rt)
            aux_sum = aux_sum + aux
            if collect_cache:
                slots_out[f"slot{s}"] = new_slot
        return x, (slots_out, aux_sum) if collect_cache else aux_sum

    x, ys = _stack_scan(cfg, params, x, body,
                        remat=cfg.remat and not collect_cache)
    if collect_cache:
        caches, auxs = ys
        aux_total = jnp.sum(auxs)
    else:
        caches = None
        aux_total = jnp.sum(ys) if ys is not None else jnp.float32(0.0)
    with jax.named_scope("head"):
        x = norm_apply(cfg, params["final_norm"], x)
    return x, caches, aux_total


def loss_fn(cfg, params, batch, *, rt=None):
    """batch: {"tokens": (B,S), "targets": (B,S), optional "patch_embeds",
    "mask"}. Returns scalar loss."""
    x, _, aux = forward(cfg, params, batch["tokens"],
                        patch_embeds=batch.get("patch_embeds"), rt=rt)
    S_t = batch["targets"].shape[1]
    x = x[:, -S_t:]  # VLM: loss only over text positions
    if cfg.logit_scale != 1.0:
        x = x * jnp.asarray(cfg.logit_scale, x.dtype)
    w = unembed_matrix(cfg, params)
    nll = layers.chunked_xent(x, w, batch["targets"], chunk=cfg.vocab_chunk,
                              mask=batch.get("mask"))
    return nll + 0.01 * aux


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    kinds = slot_kinds(cfg)
    n_super = cfg.num_layers // len(kinds)

    def stack(t):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_super,) + a.shape), t)

    cache = {f"slot{s}": stack(_mix_cache_init(cfg, kind, batch, max_seq,
                                               dtype))
             for s, kind in enumerate(kinds)}
    cache["lengths"] = jnp.zeros((batch,), jnp.int32)
    return cache


def prefill(cfg, params, tokens, *, max_seq: int, patch_embeds=None, rt=None,
            window=None, last_pos=None, true_len=None):
    """Process the prompt; build the decode cache. Returns (logits_last, cache).

    ``last_pos``/``true_len`` support right-padded (bucketed) prompts:
    logits are gathered at each sequence's own last real position instead of
    ``S-1``, and the cache lengths record the real (unpadded) lengths.
    Causal attention makes right padding invisible to the real positions.

    Device work sits under the named scopes ``qkv``, ``attn``, ``ffn``
    (``_block_apply``) and ``head`` (the final norm and the head); the
    prefill engine puts them under ``prefill``."""
    x, caches, _ = forward(cfg, params, tokens, patch_embeds=patch_embeds,
                           rt=rt, collect_cache=True, window=window)
    B, S = x.shape[:2]
    kinds = slot_kinds(cfg)
    cache = init_cache(cfg, B, max_seq,
                       jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    for s, kind in enumerate(kinds):
        mix = kind.split("+")[0]
        got = caches[f"slot{s}"]
        if mix == "attn":
            k_new, v_new = got  # (n_super, B, S, Hkv, hd)
            hkv_s = cfg.decode_cache_layout == "hkv_s"
            s_cache = cache[f"slot{s}"]["k"].shape[3 if hkv_s else 2]
            if cfg.sliding_window and s_cache == cfg.sliding_window and \
                    S > s_cache:
                # keep last `window` tokens, ROTATED so token at absolute
                # position p lands at ring index p % window (decode's
                # ring-buffer writes overwrite the oldest entry).
                k_new = jnp.roll(k_new[:, :, -s_cache:], S % s_cache, axis=2)
                v_new = jnp.roll(v_new[:, :, -s_cache:], S % s_cache, axis=2)
            upd_len = min(S, s_cache)
            k_upd, v_upd = k_new[:, :, :upd_len], v_new[:, :, :upd_len]
            if hkv_s:  # one transpose at the phase handoff, amortized
                k_upd = k_upd.transpose(0, 1, 3, 2, 4)
                v_upd = v_upd.transpose(0, 1, 3, 2, 4)
            cache[f"slot{s}"]["k"] = lax.dynamic_update_slice(
                cache[f"slot{s}"]["k"],
                k_upd.astype(cache[f"slot{s}"]["k"].dtype), (0, 0, 0, 0, 0))
            cache[f"slot{s}"]["v"] = lax.dynamic_update_slice(
                cache[f"slot{s}"]["v"],
                v_upd.astype(cache[f"slot{s}"]["v"].dtype), (0, 0, 0, 0, 0))
        else:
            cache[f"slot{s}"] = got
    cache["lengths"] = (jnp.full((B,), S, jnp.int32) if true_len is None
                        else true_len.astype(jnp.int32))
    with jax.named_scope("head"):
        if last_pos is None:
            h_last = x[:, -1:]
        else:
            h_last = x[jnp.arange(B), last_pos][:, None]
        logits = head_logits(cfg, params, h_last)
    return logits, cache


def prefill_suffix(cfg, params, tokens, prefix_kv, prefix_len, *,
                   max_seq: int, rt=None, last_pos=None, true_len=None):
    """Prefill only a prompt SUFFIX against already-computed prefix KV
    (the prefix-cache partial-hit path). Returns (logits_last, cache).

    ``tokens (B, S)`` are the suffix tokens (right-padded to the bucket);
    ``prefix_kv`` maps ``slot{s}`` -> (k, v) of shape
    ``(n_super, B, P, Hkv, hd)`` — the shared prefix's KV, dequantized
    from resident pages and right-padded to ``P``; ``prefix_len (B,)``
    gives each sequence's true prefix length. Suffix queries sit at
    absolute positions ``prefix_len + i`` (RoPE included) and attend over
    [prefix ++ suffix] with prefix padding masked. The returned cache
    holds ONLY the suffix KV at rows ``0..S-1`` (lengths = suffix true
    lengths), so the bucketed wire extraction is unchanged — wire token
    ``t`` is absolute position ``prefix_len + t``, spliced onto the
    shared chain at a page boundary by the decode side. Device work sits
    under the named scopes of :func:`prefill`.

    Pure-attention stacks only (the same families the paged pool
    serves); recurrent state cannot be sliced at a position boundary.
    """
    kinds = slot_kinds(cfg)
    assert all(k.split("+")[0] == "attn" for k in kinds), \
        "prefill_suffix requires a pure-attention stack"
    assert not cfg.sliding_window, "suffix prefill assumes full attention"
    x = _embed_inputs(cfg, params, tokens)
    x = constrain(x, "batch", "seq", None)
    B, S = x.shape[:2]
    positions = prefix_len[:, None] + jnp.arange(S)[None, :]
    n_super = cfg.num_layers // len(kinds)

    def body(carry, blk_and_pkv):
        x = carry
        blk, pkv = blk_and_pkv
        slots_out = {}
        for s, kind in enumerate(kinds):
            p = blk[f"slot{s}"]
            pk, pv = pkv[f"slot{s}"]
            with jax.named_scope("qkv"):
                h = norm_apply(cfg, p["norm1"], x)
                q, k, v = layers.attn_qkv(cfg, p["attn"], h, positions)
            with jax.named_scope("attn"):
                K = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
                V = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
                o = _suffix_attention(cfg, q, K, V, prefix_len)
                out = dense(p["attn"]["wo"], o.reshape(B, S, cfg.q_dim))
            out = constrain(out, "batch", "seq", None)
            with jax.named_scope("ffn"):
                if cfg.parallel_block and "mlp" in p:
                    x = x + out + layers.mlp_apply(cfg, p["mlp"], h)
                else:
                    x = x + out
                    if kind.split("+")[1] != "none":
                        delta, _ = _apply_ffn(cfg, p, kind, x, rt)
                        x = x + delta
            x = constrain(x, "batch", "seq", None)
            slots_out[f"slot{s}"] = (k, v)
        return x, slots_out

    blocks = params["blocks"]
    if cfg.scan_layers and n_super > 1:
        x, caches = lax.scan(body, x, (blocks, prefix_kv))
    else:
        ys = []
        for i in range(n_super):
            blk_i = jax.tree.map(lambda a: a[i], blocks)
            pkv_i = jax.tree.map(lambda a: a[i], prefix_kv)
            x, y = body(x, (blk_i, pkv_i))
            ys.append(y)
        caches = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    with jax.named_scope("head"):
        x = norm_apply(cfg, params["final_norm"], x)

    cache = init_cache(cfg, B, max_seq,
                       jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    hkv_s = cfg.decode_cache_layout == "hkv_s"
    for s in range(len(kinds)):
        k_new, v_new = caches[f"slot{s}"]     # (n_super, B, S, Hkv, hd)
        s_cache = cache[f"slot{s}"]["k"].shape[3 if hkv_s else 2]
        upd_len = min(S, s_cache)
        k_upd, v_upd = k_new[:, :, :upd_len], v_new[:, :, :upd_len]
        if hkv_s:
            k_upd = k_upd.transpose(0, 1, 3, 2, 4)
            v_upd = v_upd.transpose(0, 1, 3, 2, 4)
        cache[f"slot{s}"]["k"] = lax.dynamic_update_slice(
            cache[f"slot{s}"]["k"],
            k_upd.astype(cache[f"slot{s}"]["k"].dtype), (0, 0, 0, 0, 0))
        cache[f"slot{s}"]["v"] = lax.dynamic_update_slice(
            cache[f"slot{s}"]["v"],
            v_upd.astype(cache[f"slot{s}"]["v"].dtype), (0, 0, 0, 0, 0))
    cache["lengths"] = (jnp.full((B,), S, jnp.int32) if true_len is None
                        else true_len.astype(jnp.int32))
    with jax.named_scope("head"):
        if last_pos is None:
            h_last = x[:, -1:]
        else:
            h_last = x[jnp.arange(B), last_pos][:, None]
        logits = head_logits(cfg, params, h_last)
    return logits, cache


def _suffix_attention(cfg, q, K, V, prefix_len):
    """Attention of suffix queries over [padded prefix ++ suffix] keys.

    q: (B, S, H, hd); K/V: (B, P+S, Hkv, hd). Prefix key j is absolute
    position j, valid iff j < prefix_len; suffix key j >= P is absolute
    position prefix_len + (j - P), causally visible to query i iff
    (j - P) <= i. Scores materialize — suffix buckets are small by
    construction (that is the point of the partial hit)."""
    B, S, H, hd = q.shape
    Sk, Hk = K.shape[1], K.shape[2]
    P = Sk - S
    g = H // Hk
    qh = q.reshape(B, S, Hk, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, K,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    j = jnp.arange(Sk)[None, None, :]
    i = jnp.arange(S)[None, :, None]
    plen = prefix_len[:, None, None]
    ok = jnp.where(j < P, j < plen, (j - P) <= i)
    bias = jnp.where(ok, 0.0, layers.NEG_INF)
    s = s + bias[:, None, None]
    w = jax.nn.softmax(s, axis=-1).astype(V.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, V,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, hd).astype(q.dtype)


def _decode_attn_hkv(cfg, q, K, V, kv_len):
    """Decode attention over a (B, Hkv, S, hd) cache — contraction dim
    innermost on both operands, so no transposed KV copy materializes
    (mirrors the Pallas flash-decode kernel's access pattern)."""
    B = q.shape[0]
    Hkv, g = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    S = K.shape[2]
    qr = q.reshape(B, Hkv, g, cfg.head_dim)
    s = jnp.einsum("bhgd,bhkd->bhgk", qr, K,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(cfg.head_dim)
    if cfg.attn_logit_softcap:
        s = cfg.attn_logit_softcap * jnp.tanh(s / cfg.attn_logit_softcap)
    mask = jnp.arange(S)[None, None, None, :] < kv_len[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(V.dtype)
    o = jnp.einsum("bhgk,bhkd->bhgd", w, V,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, cfg.num_heads, cfg.head_dim).astype(q.dtype)


def decode_step_inplace(cfg, params, cache, tokens, *, rt=None, window=None):
    """One decode step with IN-PLACE cache updates (§Perf optimization).

    The scan-based ``decode_step`` re-materializes each layer's full KV slice
    through the ys-stacking mechanism (~2x full-cache traffic per step). This
    variant runs a ``fori_loop`` over superblocks and scatters the new token
    DIRECTLY into the stacked cache buffer (donated by the launcher), so the
    per-step HBM traffic collapses to: params + one cache READ + one-token
    writes — the true decode roofline.

    Restriction: attention slots only take this fast path; recurrent slots
    (mamba/xlstm states) are small and use slice+update, which XLA keeps
    in-place on the loop carry.
    """
    kinds = slot_kinds(cfg)
    x = _embed_inputs(cfg, params, tokens)
    B = x.shape[0]
    positions = cache["lengths"][:, None]
    x = constrain(x, "batch", None, None)
    n_super = cfg.num_layers // len(kinds)
    block_cache = {k: v for k, v in cache.items() if k != "lengths"}
    bidx = jnp.arange(B)

    def body(i, carry):
        x, bc = carry
        blk = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), params["blocks"])
        for s, kind in enumerate(kinds):
            mix = kind.split("+")[0]
            p = blk[f"slot{s}"]
            h = norm_apply(cfg, p["norm1"], x)
            if mix == "attn":
                slot = bc[f"slot{s}"]
                hkv_s = cfg.decode_cache_layout == "hkv_s"
                s_cache = slot["k"].shape[3 if hkv_s else 2]
                q, k, v = layers.attn_qkv(cfg, p["attn"], h, positions)
                ring = cfg.sliding_window and s_cache == cfg.sliding_window
                w_pos = (positions[:, 0] % s_cache) if ring \
                    else positions[:, 0]
                if hkv_s:
                    # flash-decode layout: scatter at [layer, b, :, pos]
                    slot["k"] = slot["k"].at[i, bidx, :, w_pos].set(k[:, 0])
                    slot["v"] = slot["v"].at[i, bidx, :, w_pos].set(v[:, 0])
                    K = lax.dynamic_index_in_dim(slot["k"], i, 0,
                                                 keepdims=False)
                    V = lax.dynamic_index_in_dim(slot["v"], i, 0,
                                                 keepdims=False)
                    klen = (jnp.minimum(positions[:, 0] + 1, s_cache)
                            if ring else positions[:, 0] + 1)
                    o = _decode_attn_hkv(cfg, q, K, V, klen)
                else:
                    # scatter ONE token straight into the stacked buffer
                    slot["k"] = slot["k"].at[i, bidx, w_pos].set(k[:, 0])
                    slot["v"] = slot["v"].at[i, bidx, w_pos].set(v[:, 0])
                    K = lax.dynamic_index_in_dim(slot["k"], i, 0,
                                                 keepdims=False)
                    V = lax.dynamic_index_in_dim(slot["v"], i, 0,
                                                 keepdims=False)
                    if ring:
                        klen = jnp.minimum(positions[:, 0] + 1, s_cache)
                        o = layers.attention(q, K, V, causal=False, window=0,
                                             kv_len=klen,
                                             softcap=cfg.attn_logit_softcap,
                                             chunk_q=cfg.attn_chunk_q,
                                             chunk_kv=cfg.attn_chunk_kv)
                    else:
                        o = layers.attention(q, K, V, causal=True,
                                             window=window or
                                             cfg.sliding_window,
                                             kv_len=positions[:, 0] + 1,
                                             q_offset=positions[:, 0],
                                             softcap=cfg.attn_logit_softcap,
                                             chunk_q=cfg.attn_chunk_q,
                                             chunk_kv=cfg.attn_chunk_kv)
                out = dense(p["attn"]["wo"], o.reshape(B, 1, cfg.q_dim))
                bc[f"slot{s}"] = slot
                x_new = x + out
                if cfg.parallel_block and "mlp" in p:
                    x_new = x_new + layers.mlp_apply(cfg, p["mlp"], h)
                    x = x_new
                else:
                    x = x_new
                    if kind.split("+")[1] != "none":
                        delta, _ = _apply_ffn(cfg, p, kind, x, rt)
                        x = x + delta
            else:
                csl = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                    a, i, 0, keepdims=False), bc[f"slot{s}"])
                x, new_slot, _ = _block_apply(
                    cfg, p, kind, x, positions=positions, mode="step",
                    cache_slot=csl, q_offset=None, kv_len=None,
                    window=window, rt=rt)
                bc[f"slot{s}"] = jax.tree.map(
                    lambda buf, ns: lax.dynamic_update_index_in_dim(
                        buf, ns.astype(buf.dtype), i, 0),
                    bc[f"slot{s}"], new_slot)
        return (x, bc)

    x, block_cache = lax.fori_loop(0, n_super, body, (x, block_cache))
    x = norm_apply(cfg, params["final_norm"], x)
    logits = head_logits(cfg, params, x)
    new_cache = dict(block_cache)
    new_cache["lengths"] = cache["lengths"] + 1
    return logits, new_cache


def decode_step(cfg, params, cache, tokens, *, rt=None, window=None):
    """One decode step. tokens: (B, 1). Returns (logits, new_cache)."""
    if (rt is not None and rt.inplace_decode) or \
            cfg.decode_cache_layout == "hkv_s":
        return decode_step_inplace(cfg, params, cache, tokens, rt=rt,
                                   window=window)
    kinds = slot_kinds(cfg)
    x = _embed_inputs(cfg, params, tokens)
    B = x.shape[0]
    positions = cache["lengths"][:, None]  # (B,1) absolute positions
    x = constrain(x, "batch", None, None)

    def body(carry, blk_and_cache):
        x = carry
        blk, csl = blk_and_cache
        new_slots = {}
        for s, kind in enumerate(kinds):
            x, new_slot, _ = _block_apply(
                cfg, blk[f"slot{s}"], kind, x, positions=positions,
                mode="step", cache_slot=csl[f"slot{s}"], q_offset=None,
                kv_len=None, window=window, rt=rt)
            new_slots[f"slot{s}"] = new_slot
        return x, new_slots

    block_cache = {k: v for k, v in cache.items() if k != "lengths"}
    kinds_n = len(kinds)
    n_super = cfg.num_layers // kinds_n
    if cfg.scan_layers and n_super > 1:
        x, new_cache = lax.scan(body, x, (params["blocks"], block_cache))
    else:
        ys = []
        for i in range(n_super):
            blk_i = jax.tree.map(lambda a: a[i], params["blocks"])
            csl_i = jax.tree.map(lambda a: a[i], block_cache)
            x, y = body(x, (blk_i, csl_i))
            ys.append(y)
        new_cache = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    x = norm_apply(cfg, params["final_norm"], x)
    logits = head_logits(cfg, params, x)
    new_cache["lengths"] = cache["lengths"] + 1
    return logits, new_cache
