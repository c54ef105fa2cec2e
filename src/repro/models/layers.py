"""Core JAX layers shared by every architecture family.

Pure-functional: params are nested dicts of jnp arrays. The attention here is
the jnp reference/production-CPU path; the Pallas TPU kernels in
``repro.kernels`` are selected by ``ops`` wrappers when running on TPU.

The blocked attention (`attention`) is flash-structured (online softmax over
KV chunks inside a scan) so the lowered HLO has flash-like memory behaviour —
this is what the dry-run rooflines see.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x, scale, bias=None, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def norm_apply(cfg, p, x):
    if cfg.norm_type == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p.get("bias"))


def norm_init(cfg, d):
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# Rotary position embeddings (with partial-rotary support)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, rotary_pct: float, theta: float):
    rot_dim = int(head_dim * rotary_pct) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    return inv, rot_dim


def apply_rope(x, positions, *, rotary_pct: float = 1.0, theta: float = 10000.0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    if theta <= 0.0:
        return x  # NoPE archs (jamba) / abs-pos archs (whisper, xlstm)
    hd = x.shape[-1]
    inv, rot_dim = rope_freqs(hd, rotary_pct, theta)
    if rot_dim == 0:
        return x
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, rot/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    xr, xp = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = xr[..., : rot_dim // 2], xr[..., rot_dim // 2:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if rot_dim < hd else out


def sinusoidal_pos(positions, d_model: int):
    half = d_model // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Dense / projection helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in, d_out, *, bias=False, scale=None, dtype=jnp.bfloat16):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------


def mlp_init(cfg, key, d_model=None, d_ff=None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2 = jax.random.split(key)
    gated = cfg.mlp_type in ("silu", "geglu")
    wi = dense_init(k1, d, (2 * f) if gated else f, bias=cfg.mlp_bias)
    wo = dense_init(k2, f, d, bias=cfg.mlp_bias, scale=1.0 / math.sqrt(f))
    return {"wi": wi, "wo": wo}


def mlp_apply(cfg, p, x):
    h = dense(p["wi"], x)
    if cfg.mlp_type in ("silu", "geglu"):
        g, u = jnp.split(h, 2, axis=-1)
        act = jax.nn.silu(g) if cfg.mlp_type == "silu" else jax.nn.gelu(g)
        h = act * u
    else:
        h = jax.nn.gelu(h)
    return dense(p["wo"], h)


# ---------------------------------------------------------------------------
# Blocked (flash-structured) attention — jnp path
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _mask_bias(qpos, kpos, *, causal, window, kv_len):
    """(..., Sq, Sk) additive bias from causal / sliding-window / length mask."""
    m = jnp.zeros(qpos.shape[:-1] + (qpos.shape[-1], kpos.shape[-1]),
                  jnp.float32)
    qp = qpos[..., :, None]
    kp = kpos[..., None, :]
    ok = jnp.ones_like(m, dtype=bool)
    if causal:
        ok &= kp <= qp
    if window and window > 0:
        ok &= kp > qp - window
    if kv_len is not None:
        ok &= kp < kv_len[..., None, None]
    return jnp.where(ok, m, NEG_INF)


def attention_ref(q, k, v, *, causal=True, window=0, softcap=0.0,
                  q_offset=None, kv_len=None):
    """Reference attention, materializes scores. q:(B,Sq,H,hd) k/v:(B,Sk,Hk,hd)."""
    B, Sq, H, hd = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    g = H // Hk
    qh = q.reshape(B, Sq, Hk, g, hd)
    # bf16 operands, f32 accumulation (MXU-native; avoids materializing f32
    # copies of Q/K/V — critical for the decode KV-cache memory roofline)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = (jnp.arange(Sq) if q_offset is None
            else q_offset[:, None] + jnp.arange(Sq)[None, :])
    if q_offset is None:
        qpos = jnp.broadcast_to(qpos, (B, Sq))
    kpos = jnp.broadcast_to(jnp.arange(Sk), (B, Sk))
    bias = _mask_bias(qpos, kpos, causal=causal, window=window, kv_len=kv_len)
    s = s + bias[:, None, None]
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def attention(q, k, v, *, causal=True, window=0, softcap=0.0,
              q_offset=None, kv_len=None, chunk_q=512, chunk_kv=1024):
    """Flash-structured blocked attention.

    Outer scan over query chunks, inner scan over KV chunks with an online
    softmax (running max / sum / accumulator), so no (Sq, Sk) score tensor is
    ever materialized. Used for both prefill (Sq large) and decode (Sq == 1).
    """
    B, Sq, H, hd = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    g = H // Hk
    if Sq * Sk <= 256 * 256:  # small: reference path is cheaper than scans
        return attention_ref(q, k, v, causal=causal, window=window,
                             softcap=softcap, q_offset=q_offset, kv_len=kv_len)
    cq = min(chunk_q, Sq)
    ck = min(chunk_kv, Sk)
    nq, nk = -(-Sq // cq), -(-Sk // ck)
    pq, pk = nq * cq - Sq, nk * ck - Sk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    # effective kv length masks out kv padding
    eff_len = (jnp.full((B,), Sk, jnp.int32) if kv_len is None
               else kv_len.astype(jnp.int32))
    qoff = jnp.zeros((B,), jnp.int32) if q_offset is None else q_offset

    qb = qp.reshape(B, nq, cq, Hk, g, hd).transpose(1, 0, 2, 3, 4, 5)
    kb = kp.reshape(B, nk, ck, Hk, hd).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nk, ck, Hk, hd).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / math.sqrt(hd)

    def q_step(_, qi_blk):
        qi, blk = qi_blk  # qi: scalar block idx; blk: (B, cq, Hk, g, hd)
        qpos = qoff[:, None] + qi * cq + jnp.arange(cq)[None, :]  # (B, cq)

        def kv_step(carry, kv_blk):
            m, l, acc = carry
            ki, kblk, vblk = kv_blk
            kpos = ki * ck + jnp.arange(ck)[None, :]  # (1, ck) -> broadcast
            kpos = jnp.broadcast_to(kpos, (B, ck))
            # bf16 operands + f32 accumulation (no f32 copies of K/V blocks)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", blk, kblk,
                           preferred_element_type=jnp.float32) * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            bias = _mask_bias(qpos, kpos, causal=causal, window=window,
                              kv_len=eff_len)
            s = s + bias[:, None, None]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hk, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hk, g, cq), jnp.float32)
        a0 = jnp.zeros((B, Hk, g, cq, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, o.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B,cq,Hk,g,hd)

    _, ob = lax.scan(q_step, None, (jnp.arange(nq), qb))
    o = ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * cq, H, hd)
    return o[:, :Sq]


# ---------------------------------------------------------------------------
# Attention block (projections + rope + cache handling)
# ---------------------------------------------------------------------------


def attn_init(cfg, key, *, cross=False):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 5)
    p = {
        "wq": dense_init(ks[0], d, qd, bias=cfg.attn_bias),
        "wk": dense_init(ks[1], d, kvd, bias=cfg.attn_bias),
        "wv": dense_init(ks[2], d, kvd, bias=cfg.attn_bias),
        "wo": dense_init(ks[3], qd, d, bias=cfg.attn_bias,
                         scale=1.0 / math.sqrt(qd)),
    }
    if cfg.qk_norm:
        p["qnorm"] = {"scale": jnp.zeros((cfg.head_dim,), jnp.float32)}
        p["knorm"] = {"scale": jnp.zeros((cfg.head_dim,), jnp.float32)}
    return p


def attn_qkv(cfg, p, x, positions):
    from repro.distributed.sharding import constrain
    B, S, _ = x.shape
    q = dense(p["wq"], x).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = dense(p["wk"], x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = dense(p["wv"], x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    # no-ops under DEFAULT rules; the ATTN_QSEQ variant shards Q on sequence
    # and force-replicates the (small) MQA/GQA KV — constrained both before
    # and after rope so SPMD never invents a head_dim split in between
    q = constrain(q, "batch", "qseq", "heads", "kseq")
    k = constrain(k, "batch", None, "kv_heads", "kseq")
    v = constrain(v, "batch", None, "kv_heads", "kseq")
    if cfg.qk_norm:
        q = rmsnorm(q, p["qnorm"]["scale"])
        k = rmsnorm(k, p["knorm"]["scale"])
    q = apply_rope(q, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
    k = apply_rope(k, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
    q = constrain(q, "batch", "qseq", "heads", "kseq")
    k = constrain(k, "batch", None, "kv_heads", "kseq")
    return q, k, v


def attn_out(cfg, p, q, k, v, *, causal=True, window=None, kv_len=None,
             q_offset=None):
    """Attention of ``q (B, S, H, hd)`` over ``k``/``v`` and the output
    projection."""
    win = cfg.sliding_window if window is None else window
    o = attention(q, k, v, causal=causal, window=win, kv_len=kv_len,
                  softcap=cfg.attn_logit_softcap, q_offset=q_offset,
                  chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv)
    B, S = q.shape[:2]
    return dense(p["wo"], o.reshape(B, S, cfg.q_dim))


def attn_apply(cfg, p, x, *, positions, causal=True, window=None,
               kv_cache=None, kv_len=None, q_offset=None):
    """Self-attention. If kv_cache=(K,V) given, attends over cache (decode).

    Returns (out, (k_new, v_new)) where k_new/v_new are this call's fresh KV
    (for cache insertion by the caller).
    """
    q, k, v = attn_qkv(cfg, p, x, positions)
    K, V = kv_cache if kv_cache is not None else (k, v)
    out = attn_out(cfg, p, q, K, V, causal=causal, window=window,
                   kv_len=kv_len, q_offset=q_offset)
    return out, (k, v)


# ---------------------------------------------------------------------------
# Depthwise causal conv1d (mamba / xlstm)
# ---------------------------------------------------------------------------


def causal_conv1d(x, w, b=None, state=None):
    """x: (B, S, C); w: (K, C) depthwise; state: (B, K-1, C) carried for decode.

    Returns (y, new_state).
    """
    B, S, C = x.shape
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((B, K - 1, C), x.dtype)
    xx = jnp.concatenate([state, x], axis=1)  # (B, S+K-1, C)
    y = jnp.zeros((B, S, C), jnp.float32)
    for i in range(K):  # K is tiny (4); unrolled taps
        y = y + xx[:, i:i + S].astype(jnp.float32) * w[i].astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    new_state = xx[:, S:]
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Chunked softmax cross-entropy (never materializes (B,S,V) logits)
# ---------------------------------------------------------------------------


def chunked_xent(x, w_unembed, targets, *, chunk: int = 2048, mask=None):
    """x: (B,S,d) final hiddens; w_unembed: (d,V); targets: (B,S) int32.

    Scans over sequence chunks; per-chunk logits (B,chunk,V) live only inside
    the scan body — this is what keeps the train-step memory roofline sane for
    256k-vocab models.
    """
    B, S, d = x.shape
    c = min(chunk, S)
    n = -(-S // c)
    pad = n * c - S
    xs = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(B, n, c, d)
    ts = jnp.pad(targets, ((0, 0), (0, pad))).reshape(B, n, c)
    ms = (jnp.ones((B, S), jnp.float32) if mask is None else mask)
    ms = jnp.pad(ms, ((0, 0), (0, pad))).reshape(B, n, c)
    xs, ts, ms = (jnp.swapaxes(a, 0, 1) for a in (xs, ts, ms))

    def step(carry, xtm):
        tot, cnt = carry
        xc, tc, mc = xtm
        logits = jnp.einsum("bcd,dv->bcv", xc, w_unembed,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mc
        return (tot + nll.sum(), cnt + mc.sum()), None

    (tot, cnt), _ = lax.scan(step, (jnp.float32(0.0), jnp.float32(0.0)),
                             (xs, ts, ms))
    return tot / jnp.maximum(cnt, 1.0)
