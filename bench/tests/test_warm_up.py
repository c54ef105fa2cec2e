"""The warm-up compiles what the window runs: after it, requests of the
warmed lengths, admitted together and finishing in one decode chunk,
compile nothing (tiny size, on the CPU)."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import deploy, model
from bench.run import COMPILE_EVENT
from bench.tests.conftest import tiny_config
from repro.serving.engine import (ADMIT_FRESH, AdmissionBatch, AdmissionItem,
                                  GenRequest)

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "mistral-7b-l16.json"
LENGTHS = [9, 40, 77]


@pytest.fixture(scope="module")
def warmed():
    cfg = tiny_config(json.loads(CONFIG.read_text()))
    cfg["engine"]["max_prefill_batch"] = 1
    dep = deploy.build(model.model_config(cfg),
                       model.make_weights(cfg, 2**34 + 5), cfg["engine"])
    rng = np.random.default_rng(0)
    deploy.warm_up(dep, LENGTHS, model.dims(cfg)["V"], rng)
    return dep, rng


@pytest.mark.parametrize("k", [1, 3, 4])
def test_k_slots_released_in_one_chunk_compile_nothing(warmed, k):
    dep, rng = warmed
    gw = dep.gateway
    pre, dec = gw.pre[0].client, gw.dec[0].client
    n = []

    def count(event, duration, **_):
        if event == COMPILE_EVENT:
            n.append(duration)
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        items = []
        for i in range(k):
            req = GenRequest(-1, rng.integers(1, 200, LENGTHS[i % 3]).astype(
                np.int32), 2)
            (_, wire, first), = pre.prefill([req], compress=gw.compress,
                                            backend=gw.backend)
            items.append(AdmissionItem(req, first, ADMIT_FRESH, wire=wire))
        assert not dec.admit(AdmissionBatch(items), backend=gw.backend)
        finished = []
        while dep.decode.active:
            finished += dec.step()
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert len(finished) == k
    assert n == []
