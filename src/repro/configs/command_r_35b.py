"""command-r-35b [dense] — 40L d_model=8192 64H (MHA: kv=64) d_ff=22528 vocab=256000.

Cohere Command-R v01 (35.0B): parallel attention+FFN residual blocks under
one LayerNorm without bias, tied embeddings, logits times ``logit_scale``
0.0625, no softcap. [hf:CohereForAI/c4ai-command-r-v01]
The 08-2024 release (32B) keeps the block but has 8 KV heads and d_ff
24576; the benchmark serves it from ``bench/configs/command-r-32b-l4.json``.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="command-r-35b",
    family="dense",
    num_layers=40,
    d_model=8192,
    num_heads=64,
    num_kv_heads=64,
    head_dim=128,
    d_ff=22528,
    vocab_size=256000,
    mlp_type="silu",
    norm_type="layernorm",
    parallel_block=True,
    rope_theta=8000000.0,
    tie_embeddings=True,
    logit_scale=0.0625,
)


def reduced() -> ModelConfig:
    return CONFIG.replace(
        name="command-r-35b-smoke", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
        attn_chunk_q=16, attn_chunk_kv=16, vocab_chunk=32, remat=False)
