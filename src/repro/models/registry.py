"""Uniform model API over all families + abstract input specs for the dry-run.

``build(cfg)`` returns a ``ModelApi`` with:
  init(key) -> params
  loss(params, batch) -> scalar            (train_4k)
  prefill(params, batch) -> (logits, cache) (prefill_32k)
  decode(params, cache, batch) -> (logits, cache) (decode_32k / long_500k)
  input_specs(shape) -> batch of ShapeDtypeStructs (no allocation)
  cache_specs(shape) -> abstract decode cache
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import paged, transformer, whisper


@dataclass
class ModelApi:
    cfg: ModelConfig
    init: Callable
    loss: Callable
    prefill: Callable
    decode: Callable
    input_specs: Callable
    cache_specs: Callable
    decode_chunk: Optional[Callable] = None
    # partial-prefix-hit path: prefill only a suffix against dequantized
    # prefix KV (transformer.prefill_suffix). None for stacks that can't
    # slice their state at a position boundary (recurrent, audio, SWA).
    prefill_suffix: Optional[Callable] = None
    # factory for the paged (page-table, int4-at-rest) decode path:
    # paged_decode_fns(page_size, backend) -> (step_fn, chunk_fn) with the
    # layout knobs closed over (they must be static under jit). None when
    # the arch can't page its cache (recurrent state, SWA ring buffers,
    # audio, softcap).
    paged_decode_fns: Optional[Callable] = None


def make_decode_chunk(decode_fn: Callable) -> Callable:
    """Build a device-resident greedy multi-token decode loop around a
    single-step ``decode(params, cache, batch) -> (logits, cache)``.

    The returned function runs ``n_steps`` decode steps as one ``lax.scan``
    — current tokens, per-slot EOS / max-token / max-seq done-flags, and the
    emitted token chunk all stay on device, so a serving engine pays one
    host round-trip per *chunk* instead of per token (DESIGN.md §3).

    state pytree: {"cur": (B,) int32 current token per slot,
                   "active": (B,) bool slot-occupied & not finished,
                   "n_out": (B,) int32 tokens emitted so far (incl. first),
                   "max_new": (B,) int32 per-request budget}.
    Returns (tokens (n_steps, B), valid (n_steps, B) bool, cache, state).
    Inactive slots keep their cache lengths frozen so free slots never
    advance; a slot's final token is emitted on the step that finishes it.
    """

    def decode_chunk(params, cache, state, *, n_steps: int, eos_id: int,
                     max_seq: int):
        max_new = state["max_new"]

        def step(carry, _):
            cache, cur, active, n_out = carry
            logits, new_cache = decode_fn(params, cache,
                                          {"tokens": cur[:, None]})
            with jax.named_scope("decode/head"):
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            n_out = n_out + active.astype(jnp.int32)
            new_cache = dict(new_cache)
            new_cache["lengths"] = jnp.where(active, new_cache["lengths"],
                                             cache["lengths"])
            done = active & ((nxt == eos_id) | (n_out >= max_new)
                             | (new_cache["lengths"] >= max_seq - 1))
            emit = jnp.where(active, nxt, -1)
            cur = jnp.where(active, nxt, cur)
            return (new_cache, cur, active & ~done, n_out), (emit, active)

        carry = (cache, state["cur"], state["active"], state["n_out"])
        (cache, cur, active, n_out), (toks, valid) = jax.lax.scan(
            step, carry, None, length=n_steps)
        return toks, valid, cache, {"cur": cur, "active": active,
                                    "n_out": n_out, "max_new": max_new}

    return decode_chunk


def _effective_cfg(cfg: ModelConfig, shape: Optional[ShapeSpec]) -> ModelConfig:
    """Per-cell adjustments (documented in DESIGN.md):

    * jamba long_500k: its 4 attention layers fall back to SWA(4096) so the
      decode state is bounded (Mamba layers already are O(1)).
    """
    if shape is None:
        return cfg
    if shape.name == "long_500k" and cfg.family == "hybrid" \
            and not cfg.sliding_window:
        return cfg.replace(sliding_window=4096)
    return cfg


def _token_specs(cfg, shape: ShapeSpec) -> Dict[str, Any]:
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        if cfg.family == "audio":
            return {"tokens": sds((B, S), i32),
                    "targets": sds((B, S), i32),
                    "frame_embeds": sds((B, cfg.encoder_seq, cfg.d_model),
                                        jnp.bfloat16)}
        if cfg.family == "vlm":
            s_text = S - cfg.num_patches
            return {"tokens": sds((B, s_text), i32),
                    "targets": sds((B, s_text), i32),
                    "patch_embeds": sds((B, cfg.num_patches, cfg.d_model),
                                        jnp.bfloat16)}
        return {"tokens": sds((B, S), i32), "targets": sds((B, S), i32)}
    if shape.kind == "prefill":
        if cfg.family == "audio":
            return {"tokens": sds((B, S), i32),
                    "frame_embeds": sds((B, cfg.encoder_seq, cfg.d_model),
                                        jnp.bfloat16)}
        if cfg.family == "vlm":
            return {"tokens": sds((B, S - cfg.num_patches), i32),
                    "patch_embeds": sds((B, cfg.num_patches, cfg.d_model),
                                        jnp.bfloat16)}
        return {"tokens": sds((B, S), i32)}
    # decode: one new token; the KV/state cache is a separate argument
    return {"tokens": sds((B, 1), i32)}


def build(cfg: ModelConfig, *, rt: Optional[transformer.Runtime] = None
          ) -> ModelApi:
    rt = rt or transformer.Runtime()

    if cfg.family == "audio":
        def init(key):
            return whisper.init_params(cfg, key)

        def loss(params, batch):
            return whisper.loss_fn(cfg, params, batch, rt=rt)

        def prefill_fn(params, batch, *, max_seq=None):
            return whisper.prefill(cfg, params, batch["tokens"],
                                   batch["frame_embeds"],
                                   max_seq=max_seq or batch["tokens"].shape[1],
                                   rt=rt, last_pos=batch.get("last_pos"),
                                   true_len=batch.get("true_len"))

        def decode_fn(params, cache, batch):
            return whisper.decode_step(cfg, params, cache, batch["tokens"],
                                       rt=rt)

        def cache_init(batch_size, max_seq):
            return whisper.init_cache(cfg, batch_size, max_seq)
    else:
        def init(key):
            return transformer.init_params(cfg, key)

        def loss(params, batch):
            return transformer.loss_fn(cfg, params, batch, rt=rt)

        def prefill_fn(params, batch, *, max_seq=None):
            S = batch["tokens"].shape[1]
            last_pos = batch.get("last_pos")
            true_len = batch.get("true_len")
            if cfg.family == "vlm" and "patch_embeds" in batch:
                n_patch = batch["patch_embeds"].shape[1]
                S += n_patch
                # token-indexed positions shift past the patch prefix
                if last_pos is not None:
                    last_pos = last_pos + n_patch
                if true_len is not None:
                    true_len = true_len + n_patch
            return transformer.prefill(
                cfg, params, batch["tokens"], max_seq=max_seq or S,
                patch_embeds=batch.get("patch_embeds"), rt=rt,
                last_pos=last_pos, true_len=true_len)

        def decode_fn(params, cache, batch):
            return transformer.decode_step(cfg, params, cache,
                                           batch["tokens"], rt=rt)

        def cache_init(batch_size, max_seq):
            return transformer.init_cache(cfg, batch_size, max_seq)

    suffix_fn = None
    if cfg.family != "audio" and paged.paged_supported(cfg):
        def suffix_fn(params, batch, *, max_seq=None):
            S = batch["tokens"].shape[1]
            return transformer.prefill_suffix(
                cfg, params, batch["tokens"], batch["prefix_kv"],
                batch["prefix_len"], max_seq=max_seq or S, rt=rt,
                last_pos=batch.get("last_pos"),
                true_len=batch.get("true_len"))

    def input_specs(shape: ShapeSpec):
        ecfg = _effective_cfg(cfg, shape)
        return _token_specs(ecfg, shape)

    def cache_specs(shape: ShapeSpec):
        ecfg = _effective_cfg(cfg, shape)
        init_fn = (whisper.init_cache if ecfg.family == "audio"
                   else transformer.init_cache)
        return jax.eval_shape(
            lambda: init_fn(ecfg, shape.global_batch, shape.seq_len))

    paged_fns = None
    if paged.paged_supported(cfg):
        def paged_fns(page_size: int, backend: str = "auto"):
            def step_fn(params, cache, batch):
                return paged.decode_step_paged(
                    cfg, params, cache, batch["tokens"], rt=rt,
                    page_size=page_size, backend=backend)
            return step_fn, make_decode_chunk(step_fn)

    return ModelApi(cfg=cfg, init=init, loss=loss, prefill=prefill_fn,
                    decode=decode_fn, input_specs=input_specs,
                    cache_specs=cache_specs,
                    decode_chunk=make_decode_chunk(decode_fn),
                    prefill_suffix=suffix_fn,
                    paged_decode_fns=paged_fns)


def build_for_cell(cfg: ModelConfig, shape: ShapeSpec,
                   rt: Optional[transformer.Runtime] = None) -> ModelApi:
    """Model with per-cell config adjustments applied (see _effective_cfg)."""
    return build(_effective_cfg(cfg, shape), rt=rt)
