"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler is installed with jaxlib, so it can refuse here what the
chip would refuse: blocks that break the (8, 128) tiling rule, kernels
that need more fast memory than they may use, a step that does not fit
the chip's 16 GiB.  Interpret mode cannot see any of that.  Each kernel
is compiled at the widths of the configuration that runs it (qwen3-1.7b
attention at S=2048 in bf16; a falcon-mamba-7b slice of the scan), and
must lower to a Mosaic kernel (``tpu_custom_call``), not to XLA ops.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this module.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as cfgs
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.models import build
from repro.runtime.serve_loop import ServeConfig, make_generate_program

HBM_BYTES = 16 * 2**30

# qwen3-1.7b attention widths (configs/qwen3_1p7b.py)
B, S, H, K, D = 1, 2048, 16, 8, 128
# a falcon-mamba-7b slice: its state dim, 1024 of its 8192 channels
MB, MS, MD, MN = 1, 2048, 1024, 16


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 host, with JAX's persistent compile cache
    off: an entry compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_fwd_compiles_to_kernel(one_chip):
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, K, D), jnp.bfloat16, one_chip)
    _assert_kernel(_compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, kv, kv))


def test_flash_fwd_bwd_compiles_to_kernel(one_chip):
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, K, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # forward, dq pass and dk/dv pass
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_decode_compiles_to_kernel(one_chip):
    q = _sds((8, 1, H, D), jnp.bfloat16, one_chip)
    cache = _sds((8, S, K, D), jnp.bfloat16, one_chip)
    idx = _sds((), jnp.int32, one_chip)
    _assert_kernel(_compile(
        lambda q, k, v, i: decode_attention(q, k, v, cache_index=i),
        q, cache, cache, idx))


def test_mamba_scan_compiles_to_kernel(one_chip):
    x = _sds((MB, MS, MD), jnp.bfloat16, one_chip)
    a = _sds((MD, MN), jnp.float32, one_chip)
    bc = _sds((MB, MS, MN), jnp.bfloat16, one_chip)
    _assert_kernel(_compile(mamba_scan, x, x, a, bc, bc))


def test_qwen3_generate_step_fits_one_chip(one_chip):
    """The served program at full width: prompt 128, 32 new tokens, batch
    8 — what ``chip_smoke.py`` serves through the farm."""
    api = build(cfgs.get("qwen3_1p7b"))
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    sc = ServeConfig(max_new_tokens=32, prompt_len=128, batch_per_task=8)
    program = make_generate_program(api, sc, params)
    payload = {"tokens": _sds((8, 128), jnp.int32, one_chip)}
    mem = _compile(program.fn, params, payload).memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 3e9 < mem.argument_size_in_bytes  # the full-width weights
    assert peak < HBM_BYTES


def test_deepseek_generate_one_prompt_compiles(one_chip):
    """DeepSeek-V2-Lite's expert share at full width serving one prompt of
    100 tokens: neither decode's 6 routed rows nor prefill's 600 fill whole
    row tiles of the grouped matmul, whose rows are padded to them."""
    api = build(cfgs.get("deepseek_v2_lite_ep4"))
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    sc = ServeConfig(max_new_tokens=8, prompt_len=100, batch_per_task=1)
    program = make_generate_program(api, sc, params)
    payload = {"tokens": _sds((1, 100), jnp.int32, one_chip)}
    compiled = _compile(program.fn, params, payload)
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    assert 9e9 < mem.argument_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("arch", ["qwen3_1p7b", "jamba_1p5_large_398b",
                                  "falcon_mamba_7b", "deepseek_v2_lite_ep4"])
def test_decode_loop_writes_cache_in_place(one_chip, arch):
    """The bf16 generate program, compiled for the chip, moves no whole
    cache stack and no whole layer slice in its decode loop (the checks
    of ``test_decode_cache_in_place.py``), and keeps the sequence axis of
    its KV stacks next to the minor one, where decode attention reads it."""
    from test_decode_cache_in_place import (B, PROMPT, cache_traffic_faults,
                                            generate_program)

    program, params, caches = generate_program(arch, "bfloat16")
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), params)
    payload = {"tokens": _sds((B, PROMPT), jnp.int32, one_chip)}
    text = _compile(program.fn, params, payload).as_text()
    assert cache_traffic_faults(text, caches, seq_second_minor=True) == []
