"""Per-architecture smoke + prefill/decode consistency.

The consistency test is the strong one: running `prefill(prompt)` then
feeding the next tokens one-by-one through `decode` must reproduce the
logits of a single full `forward` over the whole sequence (same params,
same tokens) — this exercises KV caches, recurrent states, ring buffers,
and position handling across every family.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_reduced
from repro.models import build
from repro.models import transformer

KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, B, S, key):
    k1, k2 = jax.random.split(key)
    batch = {"tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
             "targets": jax.random.randint(k2, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "audio":
        batch["frame_embeds"] = jax.random.normal(
            k1, (B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            k1, (B, cfg.num_patches, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_train_step(arch):
    """Reduced config: one forward + one train step, output shapes, no NaNs."""
    from repro.training import optimizer as opt
    cfg = get_reduced(arch)
    api = build(cfg)
    params = api.init(KEY)
    batch = _batch_for(cfg, 2, 32, jax.random.PRNGKey(1))
    loss = api.loss(params, batch)
    assert np.isfinite(float(loss)), arch
    step = opt.make_train_step(api, opt.AdamWConfig(lr=1e-3))
    p2, o2, stats = step(params, opt.adamw_init(params), batch)
    assert np.isfinite(float(stats["loss"]))
    assert np.isfinite(float(stats["grad_norm"]))
    # params actually changed
    l0 = jax.tree.leaves(params)[0]
    l1 = jax.tree.leaves(p2)[0]
    assert l0.shape == l1.shape
    assert not np.allclose(np.asarray(l0, np.float32),
                           np.asarray(l1, np.float32))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_matches_forward(arch):
    """Greedy-context equivalence: forward logits == prefill+decode logits."""
    cfg = get_reduced(arch)
    api = build(cfg)
    params = api.init(KEY)
    B, S_prompt, S_total = 2, 12, 16
    batch = _batch_for(cfg, B, S_total, jax.random.PRNGKey(2))
    tokens = batch["tokens"]

    # full forward logits at every position
    if cfg.family == "audio":
        from repro.models import whisper
        x = whisper.forward(cfg, params, tokens, batch["frame_embeds"])
        full_logits = np.asarray((x @ params["unembed"]).astype(jnp.float32))
    else:
        x, _, _ = transformer.forward(
            cfg, params, tokens, patch_embeds=batch.get("patch_embeds"))
        # the program's head, with its logit scale
        full_logits = np.asarray(transformer.head_logits(cfg, params, x))
    if cfg.family == "vlm":
        full_logits = full_logits[:, cfg.num_patches:]

    # prefill on the prompt, then step through the remaining tokens
    pf = {"tokens": tokens[:, :S_prompt]}
    for key in ("frame_embeds", "patch_embeds"):
        if key in batch:
            pf[key] = batch[key]
    logits, cache = api.prefill(params, pf,
                                max_seq=S_total + cfg.num_patches + 4)
    got = [np.asarray(logits[:, -1])]
    for t in range(S_prompt, S_total):
        logits, cache = api.decode(params, cache,
                                   {"tokens": tokens[:, t:t + 1]})
        got.append(np.asarray(logits[:, -1]))
    got = np.stack(got, axis=1)  # (B, S_total-S_prompt+1, V)
    want = full_logits[:, S_prompt - 1:S_total]
    # bf16 models: compare top-1 agreement + moderate numeric tolerance
    top_got = got.argmax(-1)
    top_want = want.argmax(-1)
    agree = (top_got == top_want).mean()
    assert agree >= 0.95, (arch, agree)
    np.testing.assert_allclose(got, want, atol=0.25, rtol=0.05)


def test_sliding_window_ring_buffer_consistency():
    """SWA decode with a ring cache == full-cache attention w/ window mask."""
    cfg = get_reduced("h2o-danube-3-4b").replace(sliding_window=8)
    api = build(cfg)
    params = api.init(KEY)
    B, S_prompt, S_total = 1, 12, 18
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S_total), 0,
                                cfg.vocab_size)
    x, _, _ = transformer.forward(cfg, params, tokens)
    w = transformer.unembed_matrix(cfg, params)
    full_logits = np.asarray((x @ w).astype(jnp.float32))

    logits, cache = api.prefill(params, {"tokens": tokens[:, :S_prompt]},
                                max_seq=S_total)
    # cache layout (n_super, B, S_cache, Hkv, hd): ring size == window
    assert cache["slot0"]["k"].shape[2] == cfg.sliding_window
    got = [np.asarray(logits[:, -1])]
    for t in range(S_prompt, S_total):
        logits, cache = api.decode(params, cache,
                                   {"tokens": tokens[:, t:t + 1]})
        got.append(np.asarray(logits[:, -1]))
    got = np.stack(got, axis=1)
    want = full_logits[:, S_prompt - 1:S_total]
    agree = (got.argmax(-1) == want.argmax(-1)).mean()
    assert agree >= 0.95, agree
    np.testing.assert_allclose(got, want, atol=0.25, rtol=0.05)


def test_vlm_patch_prefix_changes_logits():
    cfg = get_reduced("llava-next-mistral-7b")
    api = build(cfg)
    params = api.init(KEY)
    tokens = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    pe1 = jnp.zeros((1, cfg.num_patches, cfg.d_model), jnp.bfloat16)
    pe2 = jnp.ones((1, cfg.num_patches, cfg.d_model), jnp.bfloat16)
    l1, _ = api.prefill(params, {"tokens": tokens, "patch_embeds": pe1},
                        max_seq=32)
    l2, _ = api.prefill(params, {"tokens": tokens, "patch_embeds": pe2},
                        max_seq=32)
    assert not np.allclose(np.asarray(l1), np.asarray(l2))


def test_moe_router_load_balance_loss_positive():
    from repro.models import moe
    cfg = get_reduced("qwen3-moe-235b-a22b")
    p = moe.moe_init(cfg, KEY)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.bfloat16)
    y, aux = moe.moe_apply(cfg, p, x)
    assert y.shape == x.shape
    assert float(aux) >= 1.0 - 1e-3  # >= 1 by Cauchy-Schwarz, ==1 if balanced


@pytest.mark.parametrize("arch", ["command-r-35b", "jamba-v0.1-52b",
                                  "h2o-danube-3-4b"])
def test_inplace_decode_matches_scan_decode(arch):
    """§Perf iteration A1: the fori_loop in-place decode must be
    numerically equivalent to the scan-based decode."""
    cfg = get_reduced(arch)
    api = build(cfg)
    params = api.init(KEY)
    B, Sp, St = 2, 10, 14
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, St), 0,
                                cfg.vocab_size)
    _, c0 = api.prefill(params, {"tokens": tokens[:, :Sp]}, max_seq=St + 2)
    c1 = jax.tree.map(lambda a: a, c0)
    for t in range(Sp, St):
        l0, c0 = transformer.decode_step(cfg, params, c0,
                                         tokens[:, t:t + 1])
        l1, c1 = transformer.decode_step_inplace(cfg, params, c1,
                                                 tokens[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   atol=0.02, rtol=0.02)


def test_hkv_layout_decode_matches_default():
    """§Perf iteration A2: flash-decode cache layout equivalence."""
    cfg0 = get_reduced("command-r-35b")
    cfg1 = cfg0.replace(decode_cache_layout="hkv_s")
    api0, api1 = build(cfg0), build(cfg1)
    params = api0.init(KEY)
    B, Sp, St = 2, 10, 13
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, St), 0,
                                cfg0.vocab_size)
    _, c0 = api0.prefill(params, {"tokens": tokens[:, :Sp]}, max_seq=St + 2)
    _, c1 = api1.prefill(params, {"tokens": tokens[:, :Sp]}, max_seq=St + 2)
    for t in range(Sp, St):
        l0, c0 = api0.decode(params, c0, {"tokens": tokens[:, t:t + 1]})
        l1, c1 = api1.decode(params, c1, {"tokens": tokens[:, t:t + 1]})
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   atol=0.02, rtol=0.02)
