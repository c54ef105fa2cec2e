import json
from pathlib import Path

import pytest

from bench import flops, model

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


# Command-R 08-2024 at 4 of its 40 layers: the heavy-layer configuration
# of the cells PERF.md lists for later
COMMAND_R = {"L": 4, "d": 8192, "H": 64, "Hkv": 8, "hd": 128, "f": 22528,
             "V": 256000}


def _dims(name):
    if name == "command-r-32b-l4":
        return dict(COMMAND_R)
    return model.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_layer_weights_match_hand_counts():
    # Mistral: q 4096x4096, k and v 4096x1024, o 4096x4096, MLP 3x4096x14336
    m = _dims("mistral-7b-l16")
    assert flops.layer_matmul_params(m) == (4096 * 4096 * 2
                                            + 4096 * 1024 * 2
                                            + 3 * 4096 * 14336)
    assert flops.layer_matmul_params(m) * 2 == 436_207_616   # 436 MB bf16
    # Command-R 08-2024: q and o 8192x8192, k and v 8192x1024, MLP 3x8192x22528
    c = _dims("command-r-32b-l4")
    assert flops.layer_matmul_params(c) * 2 == 1_409_286_144  # 1.41 GB bf16


def test_prefill_flops_hand_count():
    m = _dims("mistral-7b-l16")
    n = 1024
    attn = 2 * 32 * 128 * n * (n + 1)          # QK and PV, causal
    mat = 2 * 218_103_808 * n
    head = 2 * 4096 * 32000                    # last position only
    assert flops.prefill_flops(m, n) == 16 * (mat + attn) + head
    # about 7.5 GFLOP a token over the 16 layers
    assert 7.0e9 < flops.prefill_flops(m, n) / n < 7.5e9


def test_decode_flops_hand_count():
    c = _dims("command-r-32b-l4")
    keys = 1153
    per_layer = 2 * 704_643_072 + 4 * 64 * 128 * keys
    assert flops.decode_token_flops(c, keys) == \
        4 * per_layer + 2 * 8192 * 256000
    assert list(flops.chunk_keys(100, 3)) == [101, 102, 103]


def test_kv_quant_bytes_hand_count():
    m = _dims("mistral-7b-l16")
    rows = flops.wire_rows(m, 1024, 128)
    assert rows == 2 * 16 * 1024 * 8          # K and V, 8 groups a token
    per_row = 128 * 2 + 64 + 8                # bf16 in, nibbles, scale+zero
    assert flops.kv_quant_bytes(rows, 128) == rows * per_row
    # about 86 MB for a 1024-token bucket: 0.1 ms at 819 GB/s
    pk = flops.peaks("TPU v5 lite")
    assert flops.roofline_s(0, flops.kv_quant_bytes(rows, 128), pk) == \
        pytest.approx(rows * per_row / 819e9)


def test_paged_attention_bytes_hand_count():
    c = _dims("command-r-32b-l4")
    keys = [1000, 17]
    kv = (1000 + 17) * 8 * 2 * (64 + 8)       # 8 groups of 128 a token
    qo = 2 * 2 * 64 * 128 * 2
    assert flops.paged_attn_bytes(c, keys, 128) == kv + qo
    assert flops.paged_attn_flops(c, keys) == 4 * 64 * 128 * 1017


def test_peaks_by_device_kind():
    pk = flops.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v4")
