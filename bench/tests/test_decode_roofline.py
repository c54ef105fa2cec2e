"""``decode_roofline.serve``: the least time of the traced chunks' steps
over the chunk program's device time, on hand-made runs."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import flops, model, spec
from bench.deploy import ChunkLog, SeamLog
from bench.record import Run

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
PEAKS = flops.peaks("TPU v5 lite")
READ = spec.load_reader("decode_roofline.serve")
STEP_BYTES = READ.__globals__["step_bytes"]


def _dims(name):
    return model.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


def _run(chunks, device_s, dims, traced=True):
    trace = SimpleNamespace(span_module_s={
        "bench.decode_step": {"jit_decode_chunk": device_s}})
    log = SimpleNamespace(trace_t0=0.0, trace_t1=100.0)
    return Run(log=log, seams=SeamLog(chunks=chunks), dims=dims, group=128,
               max_slots=64, peaks=PEAKS, setup_s=0.0,
               trace=trace if traced else None)


def test_command_r_step_is_bound_by_its_weights():
    """One slot at a short context: the step moves the 4 layers' matrices
    and the 256k x 8192 head, 10.2 GB of bf16, 12.5 ms at 819 GB/s."""
    k = _dims("command-r-32b-l4")
    b = STEP_BYTES(k, [100], 128)
    assert b == pytest.approx(10.23e9, rel=0.01)
    least = b / PEAKS["hbm_bytes_per_s"]
    run = _run([ChunkLog(1.0, 2.0, [99], [1])], 4 * least, k)
    assert READ(run) == pytest.approx(25.0)


def test_counts_each_step_some_slot_emitted_in():
    k = _dims("mistral-7b-l16")
    # slot 0 emits 8 tokens, slot 1 finishes after 3: steps 0-2 carry both
    chunk = ChunkLog(1.0, 2.0, [500, 40], [8, 3])
    least = 0.0
    for j in range(8):
        keys = [501 + j] + ([41 + j] if j < 3 else [])
        work = sum(flops.decode_token_flops(k, n) for n in keys)
        least += flops.roofline_s(work, STEP_BYTES(k, keys, 128), PEAKS)
    run = _run([chunk], 2 * least, k)
    assert READ(run) == pytest.approx(50.0)


@pytest.mark.parametrize("case", ["untraced", "no_chunks", "no_time"])
def test_nothing_to_read_is_none(case):
    k = _dims("mistral-7b-l16")
    chunks = [] if case == "no_chunks" else [ChunkLog(1.0, 2.0, [10], [2])]
    run = _run(chunks, 0.0 if case == "no_time" else 1.0, k,
               traced=case != "untraced")
    assert READ(run) is None
