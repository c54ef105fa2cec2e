import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_resolves_its_files(workload):
    cell = spec.resolve(workload, ROOT)
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    assert all(callable(m.reader) for m in cell.end_to_end + cell.per_layer)
    for m in cell.per_layer:
        entry = next(e for e in BENCH["per_layer"] if e["name"] == m.name)
        assert entry["moves"] in names
    assert cell.limits["max_logit_gap"]["limit"] > 0


def test_config_files_state_their_cuts():
    for c in BENCH["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert key in f and f[key] != f["reduced"][key]


def test_readers_find_nothing_without_a_trace():
    from bench import layer_metrics as lm
    run = type("R", (), {"trace": None})()
    for fn in (lm.prefill_mfu, lm.kv_quant_roofline, lm.decode_mfu,
               lm.paged_attn_roofline, lm.device_idle_share):
        assert fn(run) is None


def test_new_cell_is_new_files_only(tmp_path):
    """A configuration, a mix, a metric and a cell added as new files and
    new entries resolve without touching an existing file."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((ROOT / "bench/configs/mistral-7b-l16.json").read_text())
    cfg["name"] = "throwaway-l2"
    cfg["num_hidden_layers"] = 2
    (tmp_path / "bench/configs/throwaway-l2.json").write_text(
        json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/conversation.json").read_text())
    mix["arrival"]["rate"] = 9.0
    (tmp_path / "bench/traffic/burst.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/throwaway_share.py").write_text(
        "def read(run):\n    return 42.0\n")
    (tmp_path / "bench/limits/throwaway-burst.json").write_text(
        json.dumps({"max_logit_gap": {"limit": 0.5}}))
    bench["configs"].append({"name": "throwaway-l2", "source": cfg["source"],
                             "file": "bench/configs/throwaway-l2.json",
                             "reduced": ["num_hidden_layers"], "why": "t"})
    bench["workloads"].append({"name": "throwaway-burst",
                               "config": "throwaway-l2", "traffic": "burst",
                               "chips": 1, "why": "t"})
    bench["end_to_end"][0]["workloads"].append("throwaway-burst")
    bench["per_layer"].append({"name": "throwaway_share", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "ttft_p95_s",
                               "workloads": ["throwaway-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve("throwaway-burst", tmp_path)
    assert cell.config["num_hidden_layers"] == 2
    assert cell.traffic["arrival"]["rate"] == 9.0
    assert [m.name for m in cell.per_layer] == ["throwaway_share"]
    assert cell.per_layer[0].reader(None) == 42.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def _run(cwd, env_extra=None):
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mistral7b-conversation", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=240)


def test_run_on_cpu_fails_and_names_the_platform():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert "{" not in p.stdout


def test_run_without_the_program_fails(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
