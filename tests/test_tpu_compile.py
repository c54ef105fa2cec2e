"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e that is described, not attached, at gemma-2b's published widths (and
the paged decode kernel at the benchmark's Mistral-7B and Command-R engine
shapes too, with Command-R's whole decode chunk at its engine's sizes).

Interpret mode accepts casts and reshapes that the chip's compiler
(Mosaic) refuses, so the kernel tests elsewhere cannot catch those. Each
compile here is what the chip would compile, and fails the same way. The
topology is described inside a fixture, never at import: only one process
may load the TPU compiler library.
"""
import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import model as bench_model
from repro.configs import get_config
from repro.kernels import ops
from repro.models import build, paged
from repro.serving.engine import paged_decode_jits

CFG = get_config("gemma-2b")
PAGE_SIZE = 16
MAX_SEQ = 2048
SLOTS = 4
G = paged.page_group(CFG)                       # 128
PPR = paged.groups_per_token(CFG)               # 2
ROWS = PAGE_SIZE * PPR                          # 32 rows per page
PAGES = SLOTS * paged.table_width(MAX_SEQ, PAGE_SIZE) + 1
WIRE_ROWS = 2 * CFG.num_layers * 1024 * PPR     # K+V of a 1024-token wire
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_kv_quant_compiles(spec):
    x = spec((WIRE_ROWS, G), jnp.bfloat16)
    text = _compiled_text(lambda x: ops.kv_quant(x, backend="pallas"), x)
    assert "tpu_custom_call" in text


def test_kv_dequant_compiles(spec):
    packed = spec((WIRE_ROWS, G // 2), jnp.uint8)
    scale = spec((WIRE_ROWS, 1), jnp.float32)
    text = _compiled_text(
        lambda p, s, z: ops.kv_dequant(p, s, z, backend="pallas"),
        packed, scale, scale)
    assert "tpu_custom_call" in text


# (Hkv, gq, hd, slots, table width, pages, ppr) of a decode engine's pool:
# gemma-2b as above; the benchmark's mistral-7b-l16 engine (32 slots of
# 5120 tokens, 6145 16-token pages, g 128 over 8 KV heads of 128) and its
# command-r-32b-l4 engine (64 slots, 8193 pages, 8 query heads a KV head)
PAGED_SHAPES = {
    "gemma-2b": (CFG.num_kv_heads, CFG.num_heads // CFG.num_kv_heads,
                 CFG.head_dim, SLOTS, paged.table_width(MAX_SEQ, PAGE_SIZE),
                 PAGES, PPR),
    "mistral-7b-l16": (8, 4, 128, 32, 320, 6145, 8),
    "command-r-32b-l4": (8, 8, 128, 64, 320, 8193, 8),
}


@pytest.mark.parametrize("shapes", PAGED_SHAPES)
@pytest.mark.parametrize("resident", ["int4", "bf16"])
def test_paged_decode_attention_compiles(spec, resident, shapes):
    """The kernel on a two-layer stacked pool, read at a traced layer."""
    Hkv, gq, hd, slots, W, P, ppr = PAGED_SHAPES[shapes]
    g = Hkv * hd // ppr
    q = spec((slots, Hkv, gq, hd), jnp.bfloat16)
    table = spec((slots, W), jnp.int32)
    lens = spec((slots,), jnp.int32)
    layer = spec((), jnp.int32)
    if resident == "int4":
        rows = PAGE_SIZE * ppr
        packed = spec((2, P, rows, g // 2), jnp.uint8)
        pages = {"kp": packed, "vp": packed,
                 "sz": spec((2, P, 4, rows), jnp.float32)}
    else:
        kv = spec((2, P, PAGE_SIZE, Hkv, hd), jnp.bfloat16)
        pages = {"k": kv, "v": kv}
    text = _compiled_text(
        lambda q, p, t, n, i: ops.paged_decode_attention(
            q, p, t, n, i, page_size=PAGE_SIZE, backend="pallas"),
        q, pages, table, lens, layer)
    calls = re.findall(r"%([\w.-]+) = .*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert [c.split(".")[0] for c in calls] == ["paged_decode_attention"]


def test_paged_decode_step_compiles_and_fits(spec):
    """The whole 18-layer paged int4 decode step, as the decode engine
    jits it, with the Pallas kernel inside and room left on a 16 GB chip."""
    api = build(CFG)
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: paged.init_paged_cache(
        CFG, SLOTS, MAX_SEQ, PAGES, page_size=PAGE_SIZE))
    as_spec = lambda t: jax.tree.map(lambda a: spec(a.shape, a.dtype), t)
    step, _ = api.paged_decode_fns(PAGE_SIZE, "pallas")
    compiled = jax.jit(step).lower(
        as_spec(params), as_spec(cache),
        {"tokens": spec((SLOTS, 1), jnp.int32)}).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


BENCH_CONFIGS = ("mistral-7b-l16", "command-r-32b-l4")
_SIZES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "s32": 4, "u32": 4,
          "f32": 4}


@pytest.fixture(scope="module")
def bench_chunks(spec):
    """Each benchmark configuration's paged int4 decode chunk at its
    engine's sizes, compiled as its decode engine jits it on a chip (the
    cache donated): ``name -> (compiled, pool bytes, page count)``."""
    made = {}

    def get(name):
        if name not in made:
            cfg = json.loads((Path(__file__).resolve().parents[1] / "bench"
                              / "configs" / f"{name}.json").read_text())
            eng = cfg["engine"]
            mcfg = bench_model.model_config(cfg)
            params = jax.eval_shape(
                lambda k: bench_model.make_params(cfg, k), jax.random.key(0))
            B = eng["max_slots"]
            cache = jax.eval_shape(lambda: paged.init_paged_cache(
                mcfg, B, eng["decode_max_seq"], eng["num_pages"],
                page_size=eng["page_size"]))
            state = {k: spec((B,), jnp.bool_ if k == "active" else jnp.int32)
                     for k in ("cur", "active", "n_out", "max_new")}
            as_spec = lambda t: jax.tree.map(
                lambda a: spec(a.shape, a.dtype), t)
            _, chunk = paged_decode_jits(build(mcfg), eng["page_size"],
                                         "pallas", donate=True)
            compiled = chunk.lower(
                as_spec(params), as_spec(cache), state,
                n_steps=eng["decode_chunk_steps"], eos_id=-1,
                max_seq=eng["decode_max_seq"]).compile()
            pool = sum(a.size * a.dtype.itemsize for k, slot in cache.items()
                       if k.startswith("slot") for a in slot.values())
            made[name] = (compiled, pool, eng["num_pages"])
        return made[name]
    return get


def _hlo_values(text):
    """``(name, shape dims, bytes, opcode, first operand)`` of each HLO
    instruction with an array result."""
    pat = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = (\w+)\[([\d,]*)\]"
                     r"\{[^}]*\} ([\w-]+)\(%?([\w.-]*)")
    for line in text.splitlines():
        m = pat.match(line)
        if m and m.group(2) in _SIZES:
            dims = [int(d) for d in m.group(3).split(",") if d]
            yield (m.group(1), dims, math.prod(dims) * _SIZES[m.group(2)],
                   m.group(4), m.group(5))


def test_command_r_decode_chunk_compiles_and_fits(bench_chunks):
    """``command-r-32b-l4``'s 64-slot, 8-step paged int4 decode chunk at
    its engine's page count, as the decode engine jits it: the weights
    (10.23 GB), the pool and the chunk's temporaries fit the chip."""
    compiled, _, _ = bench_chunks("command-r-32b-l4")
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("name", BENCH_CONFIGS)
def test_decode_chunk_moves_the_pool_only_in_place(bench_chunks, name):
    """The KV pool stays where it is: no copy, relayout, slice or staging
    through VMEM of a pool array (the only values of its page count
    besides the paged kernel's bitcast operands and the tail-page
    append's in-place scatters), no op under the retired
    ``decode/kv_pages`` scope, the chunk's pool output aliasing its
    donated input, and under 0.5 GB of temporaries besides the weights
    the chunk re-lays out at its entry (``wq`` and ``wk``, whose rotary
    halves XLA reads transposed, as it did before the pool moved)."""
    compiled, pool, n_pages = bench_chunks(name)
    text = compiled.as_text()
    values = list(_hlo_values(text))
    moved = [(v, op) for v, dims, _, op, _ in values if n_pages in dims
             and op in ("copy", "copy-done", "reshape", "dynamic-slice",
                        "transpose", "slice", "concatenate")]
    assert moved == [], moved
    staged = re.findall(rf"\w+\[[\d,]*\b{n_pages}\b[\d,]*\]\{{[^}}]*S\(1\)",
                        text)
    assert staged == [], staged
    assert "decode/kv_pages" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool
    relaid = [(arg, b) for _, _, b, op, arg in values
              if op == "copy" and arg.startswith("params")]
    assert all(re.search(r"_w[qk]_", arg) for arg, _ in relaid), relaid
    temp = mem.temp_size_in_bytes - sum(b for _, b in relaid)
    assert temp < 0.5e9, (mem.temp_size_in_bytes, relaid)
