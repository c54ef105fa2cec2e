import copy
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_config(cfg: dict) -> dict:
    """A configuration file shrunk to CPU size, for tests only."""
    c = copy.deepcopy(cfg)
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
             vocab_size=256)
    c["engine"].update(prefill_max_seq=256, decode_max_seq=320, max_slots=4,
                       num_pages=4 * 20 + 1, kv_group=32,
                       max_prefill_batch=2)
    return c


def tiny_mix(mix: dict) -> dict:
    """A traffic mix shrunk to CPU size, for tests only."""
    m = copy.deepcopy(mix)
    m["prompt"].update(mean=60, min=8, max=200)
    m["output"].update(mean=12, min=2, max=40)
    m["lead_in_s"] = 0.5
    m["drain_cap_s"] = 20.0
    if m["arrival"]["kind"] == "poisson":
        m["arrival"]["rate"] = 4.0
    else:
        m["arrival"]["pool"] = 12
    return m


# the program's RMSNorm fixes eps at 1e-6 (its LayerNorm takes the 1e-5
# that Command-R states); tests that hold the reference to the program's
# own forward pass set it so
PROGRAM_RMS_EPS = 1e-6


def parallel_block(cfg: dict) -> dict:
    """Command-R's block kind (08-2024 release: parallel attention and MLP
    under one LayerNorm without bias, interleaved rotary pairs, tied head)
    on a configuration file's sizes, for tests of the reference's second
    block kind."""
    c = copy.deepcopy(cfg)
    c.pop("rms_norm_eps", None)
    c.update(name=c["name"] + "-parallel", layer_norm_eps=1e-5,
             tie_word_embeddings=True,
             block={"kind": "parallel", "norm": "layernorm",
                    "parallel_residual": True, "rotary": "interleaved"})
    return c
