"""Compile-only checks for a described TPU v5e at published qwen3-0.6b
and xlstm-350m widths: the TPU compiler refuses here what interpret mode
cannot see (unaligned tiles, VMEM overuse, programs that do not fit), and
reports what a program holds.  Nothing runs; shapes only.  The topology is described inside a fixture, so importing
this file never loads the TPU library."""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.attention import flash_attention_pallas
from repro.kernels.paged_attention import (paged_decode_attention_pallas,
                                           paged_prefill_attention_pallas)
from repro.models import build_model
from repro.serving.kvcache import blocks_needed

CFG = get_config("qwen3-0.6b")
HQ, HKV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim_resolved
BATCH, CACHE_LEN, BLOCK = 4, 1024, 16
MAX_BLOCKS = blocks_needed(CACHE_LEN, BLOCK)
N_BLOCKS = BATCH * MAX_BLOCKS + 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler to describe one with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read
    # back without a chip: keep the cache off around these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _shape(s.shape, s.dtype, sharding), tree)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_kernel_compiles(one_chip):
    pool = _shape((N_BLOCKS, HKV, BLOCK, HD), jnp.bfloat16, one_chip)
    _assert_kernel(paged_decode_attention_pallas.lower(
        _shape((BATCH, HQ, 1, HD), jnp.bfloat16, one_chip), pool, pool,
        _shape((BATCH, MAX_BLOCKS), jnp.int32, one_chip),
        _shape((BATCH,), jnp.int32, one_chip)).compile())


def test_paged_prefill_kernel_compiles(one_chip):
    pool = _shape((N_BLOCKS, HKV, BLOCK, HD), jnp.bfloat16, one_chip)
    _assert_kernel(paged_prefill_attention_pallas.lower(
        _shape((1, HQ, BLOCK, HD), jnp.bfloat16, one_chip), pool, pool,
        _shape((1, MAX_BLOCKS), jnp.int32, one_chip),
        _shape((1,), jnp.int32, one_chip)).compile())


@pytest.mark.parametrize("s", [37, 200])
def test_flash_prefill_compiles_any_length(one_chip, s):
    q = _shape((1, HQ, s, HD), jnp.bfloat16, one_chip)
    kv = _shape((1, HKV, s, HD), jnp.bfloat16, one_chip)
    _assert_kernel(flash_attention_pallas.lower(q, kv, kv,
                                                causal=True).compile())


def test_decode_paged_step_compiles_at_full_width(one_chip):
    model = build_model(CFG)
    params = _on(jax.eval_shape(model.init, jax.random.key(0)), one_chip)
    cache = _on(jax.eval_shape(lambda: model.paged_cache_init(
        batch=BATCH, n_blocks=N_BLOCKS, block_size=BLOCK,
        max_blocks=MAX_BLOCKS, dtype=jnp.bfloat16)), one_chip)
    toks = _shape((BATCH, 1), jnp.int32, one_chip)
    with ops.impl_scope("pallas"):
        compiled = jax.jit(model.decode_paged, donate_argnums=(1,)).lower(
            params, cache, toks).compile()
    _assert_kernel(compiled)


def test_xlstm_decode_step_updates_state_in_place(one_chip):
    """xlstm-350m (7:1) decode at 48 slots, the cache donated as the engine
    donates it: the state is carried through the layer scans and updated
    in place, so the step needs no state-sized temporaries (10.19 GB when
    the state rode as scan xs/ys) and never copies the whole matrix
    memory."""
    cfg = dataclasses.replace(get_config("xlstm-350m"), slstm_every=8)
    model = build_model(cfg)
    slots = 48
    params = _on(jax.eval_shape(model.init, jax.random.key(0)), one_chip)
    cache = dict(model.cache_shapes(slots, 0))
    cache["pos"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    cache = _on(cache, one_chip)
    toks = _shape((slots, 1), jnp.int32, one_chip)
    compiled = jax.jit(model.decode, donate_argnums=(1,)).lower(
        params, cache, toks).compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(s.size * s.dtype.itemsize for k, s in cache.items()
                      if k != "pos")
    assert mem.temp_size_in_bytes < 1e9
    assert mem.alias_size_in_bytes >= state_bytes
    m_c = "f32[" + ",".join(map(str, cache["m_c"].shape)) + "]"
    full_copy = re.compile(re.escape(m_c) + r"\{[^}]*\} copy\(")
    assert not full_copy.search(compiled.as_text())
