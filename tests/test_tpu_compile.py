"""The served path's Pallas kernels compile for a TPU v5e chip.

Interpret-mode tests (test_kernels.py) cannot see what the chip's compiler
refuses: block shapes off the (8, 128) tiling, scratch over the VMEM limit.
These tests compile each kernel at minitron-8b widths for a v5e chip that
is described, not attached, and check that the program holds the Mosaic
kernel (``tpu_custom_call``) rather than an interpreter loop. Nothing runs,
so they say nothing about results or speed.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.decode_attention import (
    decode_attention, paged_decode_attention)
from repro.kernels.moe_gemm.moe_gemm import moe_gemm

# minitron-8b decode widths: 8 slots, GQA 32/8 heads of 128, 2048 context
B, HQ, HKV, D, S = 8, 32, 8, 128, 2048
LAYERS = 4                        # the stacked cache's depth
PAGE = 128                        # models.kvcache.DEFAULT_PAGE_SIZE
PPS = S // PAGE
D_MODEL, RANK, ADAPTERS = 4096, 8, 9   # AdapterRuntime: 8 tenants + null row


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_decode_attention_compiles(spec):
    stack = (LAYERS, B, S, HKV, D)              # models.kvcache layout
    text = _compiled_text(
        lambda q, k, v, n, i: decode_attention(q, k, v, n, i,
                                               interpret=False),
        spec((B, HQ, D), jnp.bfloat16), spec(stack, jnp.bfloat16),
        spec(stack, jnp.bfloat16), spec((B,), jnp.int32),
        spec((), jnp.int32))
    assert "tpu_custom_call" in text


def _outputs(text: str, op: str) -> list:
    """The result types of every ``op`` instruction of an HLO text."""
    return [line.split(" = ", 1)[1].split(f" {op}(", 1)[0]
            for line in text.splitlines()
            if f" {op}(" in line and " = " in line]


def _compiled_fused_decode(spec, monkeypatch, layers, slots):
    """The fused decode program of a dense model at the kv widths above:
    the engine's jitted scan of ``LM.decode_step`` (``_fused_impl``), cache
    donated, compiled for the described chip. Returns (cfg, compiled)."""
    from repro.models.config import ModelConfig
    from repro.models.transformer import LM

    cfg = ModelConfig(name=f"dense-{layers}L", family="dense",
                      num_layers=layers, d_model=256, num_heads=2 * HKV,
                      num_kv_heads=HKV, head_dim=D, d_ff=512, vocab_size=512,
                      remat="none", use_pallas_decode=True)
    lm = LM(cfg)
    # the call site picks interpret mode from the backend it sees
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def fused(params, cache, last, active):     # InferenceEngine._fused_impl
        def step(carry, _):
            c, fed = carry
            logits, c = lm.decode_step(params, c, fed[:, None],
                                       active=active)
            nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            return (c, jnp.where(active, nxt, fed)), nxt
        (cache, _), toks = jax.lax.scan(step, (cache, last), None, length=2)
        return cache, toks

    as_spec = lambda t: jax.tree.map(lambda a: spec(a.shape, a.dtype), t)
    params = as_spec(lm.param_specs())
    cache = as_spec(lm.init_cache(slots, S, abstract=True))
    return cfg, jax.jit(fused, donate_argnums=(1,)).lower(
        params, cache, spec((slots,), jnp.int32),
        spec((slots,), jnp.bool_)).compile()


def test_decode_step_reads_the_stacked_cache_in_place(spec, monkeypatch):
    """The fused decode program of a dense model hands the Pallas kernel the
    stacked cache itself: no copy of the whole stack (a relayout for a
    kernel operand) and no fusion that outputs one layer's K or V."""
    slots = 4                   # unlike HKV, so each layout has its shape
    cfg, compiled = _compiled_fused_decode(spec, monkeypatch, 2, slots)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    stack = f"bf16[{cfg.num_layers},{slots},{S},{HKV},{D}]"
    assert not [t for t in _outputs(text, "copy") if stack in t]
    layer = (f"bf16[{slots},{HKV},{S},{D}]", f"bf16[{slots},{S},{HKV},{D}]")
    assert not [t for t in _outputs(text, "fusion")
                if any(shape in t for shape in layer)]


def test_decode_step_writes_the_new_rows_in_place(spec, monkeypatch):
    """At the benchmark cell's cache layout (4 layers, 32 slots, 2048
    positions, 8 kv heads of 128) a decode step writes only its new K/V
    rows into the stacks: no select over a whole layer or stack feeds the
    write, and the program's accessed bytes stay below one K stack's
    bytes (a whole-layer select reads and writes every layer of both)."""
    slots = 32
    cfg, compiled = _compiled_fused_decode(spec, monkeypatch, LAYERS, slots)
    text = compiled.as_text()
    assert "select_dynamic-update-slice" not in text
    planes = (f"bf16[{LAYERS},{slots},{S},{HKV},{D}]",
              f"bf16[1,{slots},{S},{HKV},{D}]",
              f"bf16[{slots},{S},{HKV},{D}]")
    assert not [t for t in _outputs(text, "select")
                if any(shape in t for shape in planes)]
    from repro.launch.hlo_analysis import normalize_cost_analysis
    accessed = normalize_cost_analysis(compiled.cost_analysis())
    stack_bytes = LAYERS * slots * S * HKV * D * 2
    assert accessed["bytes accessed"] < stack_bytes


def test_paged_decode_attention_compiles(spec):
    pool = (1 + B * PPS, PAGE, HKV, D)          # models.kvcache pool layout
    text = _compiled_text(
        lambda q, k, v, n, t: paged_decode_attention(q, k, v, n, t,
                                                     interpret=False),
        spec((B, HQ, D), jnp.bfloat16), spec(pool, jnp.bfloat16),
        spec(pool, jnp.bfloat16), spec((B,), jnp.int32),
        spec((B, PPS), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("x_shape,w_shape", [
    ((ADAPTERS, B, D_MODEL), (ADAPTERS, D_MODEL, RANK)),   # h @ A
    ((ADAPTERS, B, RANK), (ADAPTERS, RANK, D_MODEL)),      # (h A) @ B
], ids=["down", "up"])
def test_adapter_grouped_gemm_compiles(spec, x_shape, w_shape):
    """The two grouped GEMMs of the adapter route on TPU
    (adapters.runtime._delta_grouped: f32, block_c = block_f = 128)."""
    text = _compiled_text(
        lambda x, w: moe_gemm(x, w, block_c=128, block_f=128,
                              interpret=False),
        spec(x_shape, jnp.float32), spec(w_shape, jnp.float32))
    assert "tpu_custom_call" in text
