"""A configuration file made into the program's model and its weights.

The benchmark makes the weights itself, on the device, in one jitted call
from ``--seed``, in the type they are served in (bf16 matrices, f32 norm
weights), laid out as the program's parameter tree. The reference reads
the same arrays through :func:`published_layer`, which maps the program's
layout onto the published one (split gate/up, norm weight ``1 + scale``
for the program's RMSNorm, rotary pairs) without importing the program.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

# weight scales: N(0, 1/fan_in) for projections, as the program's own
# initializer; norm weights 1 + N(0, NORM_STD) so the reference's norm
# handling is exercised
EMBED_STD = 0.02
NORM_STD = 0.05


def dims(cfg: dict) -> Dict[str, int]:
    hd = int(cfg.get("head_dim") or cfg["hidden_size"]
             // cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "d": int(cfg["hidden_size"]),
            "H": int(cfg["num_attention_heads"]),
            "Hkv": int(cfg["num_key_value_heads"]), "hd": hd,
            "f": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"])}


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    k = dims(cfg)
    blk = cfg["block"]
    if cfg.get("sliding_window"):
        raise ValueError("sliding-window attention does not take the paged "
                         "int4 decode path")
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=k["L"], d_model=k["d"],
        num_heads=k["H"], num_kv_heads=k["Hkv"], head_dim=k["hd"],
        d_ff=k["f"], vocab_size=k["V"], mlp_type="silu",
        norm_type=blk["norm"], parallel_block=bool(blk["parallel_residual"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), remat=False)


def seed_key(seed: int):
    """A threefry key from any whole-number seed (beyond 32 bits too)."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _norm(cfg: dict, key, shape):
    import jax
    import jax.numpy as jnp
    delta = NORM_STD * jax.random.normal(key, shape, jnp.float32)
    if cfg["block"]["norm"] == "rmsnorm":
        return {"scale": delta}          # the program applies 1 + scale
    return {"scale": 1.0 + delta}        # LayerNorm weight, no bias


def make_params(cfg: dict, key):
    """The program's parameter tree, drawn from ``key`` (call under jit)."""
    import jax
    import jax.numpy as jnp
    k = dims(cfg)
    L, d, H, Hkv, hd, f, V = (k[n] for n in ("L", "d", "H", "Hkv", "hd",
                                             "f", "V"))
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, bf) * jnp.asarray(std, bf)

    slot = {"norm1": _norm(cfg, next(ks), (L, d)),
            "attn": {"wq": {"w": normal((L, d, H * hd), 1 / math.sqrt(d))},
                     "wk": {"w": normal((L, d, Hkv * hd), 1 / math.sqrt(d))},
                     "wv": {"w": normal((L, d, Hkv * hd), 1 / math.sqrt(d))},
                     "wo": {"w": normal((L, H * hd, d),
                                        1 / math.sqrt(H * hd))}},
            "mlp": {"wi": {"w": normal((L, d, 2 * f), 1 / math.sqrt(d))},
                    "wo": {"w": normal((L, f, d), 1 / math.sqrt(f))}}}
    if not cfg["block"]["parallel_residual"]:
        slot["norm2"] = _norm(cfg, next(ks), (L, d))
    params = {"embed": normal((V, d), EMBED_STD),
              "blocks": {"slot0": slot},
              "final_norm": _norm(cfg, next(ks), (d,))}
    if not cfg["tie_word_embeddings"]:
        params["unembed"] = normal((d, V), 1 / math.sqrt(d))
    return params


def make_weights(cfg: dict, seed: int):
    """Weights on the default device, in one jitted call."""
    import jax
    return jax.block_until_ready(
        jax.jit(lambda key: make_params(cfg, key))(seed_key(seed)))


def check_tree(cfg: dict, params, program_init) -> None:
    """Raise unless ``params`` has the program's own tree, shapes and
    dtypes (a LayerNorm without bias leaves out the program's bias leaf).
    ``program_init(key)`` is the program's initializer; only its shapes
    are evaluated."""
    import jax
    want = jax.eval_shape(program_init, jax.random.PRNGKey(0))
    flat_w = {jax.tree_util.keystr(p): v for p, v in
              jax.tree_util.tree_leaves_with_path(want)}
    flat_g = {jax.tree_util.keystr(p): v for p, v in
              jax.tree_util.tree_leaves_with_path(params)}
    if cfg["block"]["norm"] == "layernorm":
        flat_w = {p: v for p, v in flat_w.items()
                  if not p.endswith("['bias']")}
    if set(flat_w) != set(flat_g):
        raise ValueError(f"weight tree differs from the program's: missing "
                         f"{sorted(set(flat_w) - set(flat_g))}, extra "
                         f"{sorted(set(flat_g) - set(flat_w))}")
    for p, v in flat_w.items():
        g = flat_g[p]
        if tuple(g.shape) != tuple(v.shape) or g.dtype != v.dtype:
            raise ValueError(f"{p}: {g.shape} {g.dtype}, program wants "
                             f"{v.shape} {v.dtype}")


def _rotary_perm(cfg: dict) -> np.ndarray:
    """Column order that turns the program's rotary pairing (i, i + hd/2)
    into the published one. Interleaved rotary pairs (2i, 2i+1): published
    column 2i is program column i, 2i+1 is program column hd/2 + i."""
    hd = int(cfg.get("head_dim") or cfg["hidden_size"]
             // cfg["num_attention_heads"])
    if cfg["block"]["rotary"] == "half":
        return np.arange(hd)
    perm = np.empty(hd, np.int64)
    perm[0::2] = np.arange(hd // 2)
    perm[1::2] = np.arange(hd // 2) + hd // 2
    return perm


def _heads_perm(cfg: dict, n_heads: int) -> np.ndarray:
    p = _rotary_perm(cfg)
    hd = len(p)
    return (np.arange(n_heads)[:, None] * hd + p[None]).reshape(-1)


def _norm_weight(cfg: dict, p):
    if cfg["block"]["norm"] == "rmsnorm":
        return 1.0 + p["scale"]
    return p["scale"]


def published_layer(cfg: dict, params, layer: int) -> dict:
    """Layer ``layer``'s weights in the published layout: ``wq (d, H*hd)``
    with the published rotary pairing, ``wk``, ``wv``, ``wo``, ``gate``,
    ``up``, ``down``, norm weights ``ln1`` (and ``ln2``)."""
    k = dims(cfg)
    s = params["blocks"]["slot0"]
    a, m = s["attn"], s["mlp"]
    wi = m["wi"]["w"][layer]
    out = {"wq": a["wq"]["w"][layer][:, _heads_perm(cfg, k["H"])],
           "wk": a["wk"]["w"][layer][:, _heads_perm(cfg, k["Hkv"])],
           "wv": a["wv"]["w"][layer], "wo": a["wo"]["w"][layer],
           "gate": wi[:, :k["f"]], "up": wi[:, k["f"]:],
           "down": m["wo"]["w"][layer],
           "ln1": _norm_weight(cfg, {"scale": s["norm1"]["scale"][layer]})}
    if "norm2" in s:
        out["ln2"] = _norm_weight(cfg, {"scale": s["norm2"]["scale"][layer]})
    return out


def published_outer(cfg: dict, params) -> dict:
    """Embedding, final norm weight and output matrix ``(d, V)``, or
    ``None`` for the output when the embedding is tied."""
    return {"embed": params["embed"],
            "ln_f": _norm_weight(cfg, params["final_norm"]),
            "unembed": params.get("unembed")}
