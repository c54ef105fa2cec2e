"""Entry points: ``chip_smoke.py`` refuses what is not a TPU checkout, its
phases run end to end at a tiny size on the CPU, and the persistent
compile cache is placed only by entry points."""
import os
import shutil
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, *, cwd=ROOT, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_cpu():
    r = _run([SMOKE], env_extra={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert r.stdout.strip() == ""          # no result line


def test_chip_smoke_refuses_outside_a_checkout(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    r = _run([str(tmp_path / "chip_smoke.py")], cwd=tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


CACHE_PROBE = ("from repro.launch.compile_cache import use_compile_cache;"
               "import jax; print(use_compile_cache());"
               "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_defaults_to_repo_dir():
    r = _run(["-c", CACHE_PROBE], env_extra={"PYTHONPATH": "src"},
             drop=("JAX_COMPILATION_CACHE_DIR",))
    want = os.path.join(ROOT, ".jax_cache")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [want, want]


def test_compile_cache_env_var_wins(tmp_path):
    r = _run(["-c", CACHE_PROBE],
             env_extra={"PYTHONPATH": "src",
                        "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]


def test_library_import_leaves_compile_cache_alone():
    import jax

    import repro.launch.compile_cache  # noqa: F401
    import repro.serving.gateway  # noqa: F401
    assert jax.config.jax_compilation_cache_dir == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR")


def test_chip_smoke_phases_tiny_on_cpu(monkeypatch):
    """The chip script's phases at gemma-2b's smoke preset, with the
    "pallas" backend run by the Pallas interpreter."""
    import jax

    from repro.configs import get_reduced
    from repro.kernels import ops
    from repro.models import build

    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke as cs
    mode = ops._mode
    monkeypatch.setattr(ops, "_mode", lambda b: "interpret"
                        if b == "pallas" else mode(b))
    monkeypatch.setattr(cs, "MAX_SEQ", 128)
    monkeypatch.setattr(cs, "BUCKETS", (16, 32, 64))
    monkeypatch.setattr(cs, "PROMPT_LENS", (16, 30, 60, 32, 40, 16, 20, 64))
    monkeypatch.setattr(cs, "MAX_NEW", 8)
    monkeypatch.setattr(cs, "RATE", 50.0)
    cfg = get_reduced("gemma-2b")
    api = build(cfg)
    params = jax.jit(api.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng([0, 1])
    assert cs.kernel_parity(cfg, 0)
    gw, prefill, decodes = cs.build_gateway(cfg, params)
    assert all(d.paged for d in decodes)
    ok, s = cs.serve(cfg, gw, decodes, rng)
    assert ok and s["n_done"] == len(cs.PROMPT_LENS)
    assert cs.decode_hlo_has_kernel(decodes[0]) is False  # jnp path on CPU
    assert cs.logits_parity(cfg, api, params, prefill, rng)
