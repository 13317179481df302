"""Compile-only checks for a described TPU v5e at published qwen3-0.6b
and xlstm-350m widths: the TPU compiler refuses here what interpret mode
cannot see (unaligned tiles, VMEM overuse, programs that do not fit), and
reports what a program holds.  Nothing runs; shapes only.  The topology is described inside a fixture, so importing
this file never loads the TPU library."""
import dataclasses
import functools
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
    pool = _shape((CFG.n_layers, N_BLOCKS, HKV, BLOCK, HD), jnp.bfloat16,
                  one_chip)
    _assert_kernel(paged_decode_attention_pallas.lower(
        _shape((BATCH, HQ, 1, HD), jnp.bfloat16, one_chip), pool, pool,
        _shape((), jnp.int32, one_chip),
        _shape((BATCH, MAX_BLOCKS), jnp.int32, one_chip),
        _shape((BATCH,), jnp.int32, one_chip)).compile())


def test_paged_prefill_kernel_compiles(one_chip):
    pool = _shape((CFG.n_layers, N_BLOCKS, HKV, BLOCK, HD), jnp.bfloat16,
                  one_chip)
    _assert_kernel(paged_prefill_attention_pallas.lower(
        _shape((1, HQ, BLOCK, HD), jnp.bfloat16, one_chip), pool, pool,
        _shape((), jnp.int32, one_chip),
        _shape((1, MAX_BLOCKS), jnp.int32, one_chip),
        _shape((1,), jnp.int32, one_chip)).compile())


@pytest.mark.parametrize("s", [37, 200])
def test_flash_prefill_compiles_any_length(one_chip, s):
    q = _shape((1, HQ, s, HD), jnp.bfloat16, one_chip)
    kv = _shape((1, HKV, s, HD), jnp.bfloat16, one_chip)
    _assert_kernel(flash_attention_pallas.lower(q, kv, kv,
                                                causal=True).compile())


# the qwen3-0.6b.decode-batch cell's engine: 16 slots, 4,096 positions a
# slot in blocks of 16, every slot's worst case reserved
CELL_BATCH, CELL_CACHE_LEN = 16, 4096
CELL_MAX_BLOCKS = blocks_needed(CELL_CACHE_LEN, BLOCK)
CELL_N_BLOCKS = CELL_BATCH * CELL_MAX_BLOCKS + 1


def _paged_step_compiled(one_chip, which):
    """``model.decode_paged`` or ``model.prefill_paged`` at the cell's
    shapes, the cache donated as the engine donates it, Pallas kernels."""
    model = build_model(CFG)
    params = _on(jax.eval_shape(model.init, jax.random.key(0)), one_chip)
    cache = _on(jax.eval_shape(lambda: model.paged_cache_init(
        batch=CELL_BATCH, n_blocks=CELL_N_BLOCKS, block_size=BLOCK,
        max_blocks=CELL_MAX_BLOCKS, dtype=jnp.bfloat16)), one_chip)
    i32 = functools.partial(_shape, dtype=jnp.int32, sharding=one_chip)
    if which == "decode":
        args = (params, cache, i32((CELL_BATCH, 1)))
    else:
        args = (params, cache, {"tokens": i32((1, BLOCK))}, i32(()),
                i32(()), i32(()))
    fn = model.decode_paged if which == "decode" else model.prefill_paged
    with ops.impl_scope("pallas"):
        return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()


_HLO_DEF = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+?)(?:\{[^}]*\})? "
                      r"([\w-]+)\(([^)]*)", re.M)


def _pool_sized_moves(hlo_text, layer_pool, stack):
    """The instructions that materialise one layer's pool, or that copy
    the stack or write more than a block of it."""
    defs = {m.group(1): (m.group(2), m.group(3), m.group(4))
            for m in _HLO_DEF.finditer(hlo_text)}
    bad = []
    for name, (shape, op, operands) in defs.items():
        if shape == layer_pool:
            bad.append((name, op))
        elif shape == stack and ("copy" in op or name.startswith("copy")):
            bad.append((name, op))
        elif shape == stack and op == "dynamic-update-slice":
            update = operands.split(",")[1].strip().lstrip("%")
            if not re.fullmatch(r"bf16\[1,1,\d+,\d+,\d+\]",
                                defs.get(update, ("?",))[0]):
                bad.append((name, op))
    return bad


def _assert_reads_the_stack_in_place(compiled):
    """No temporary reaches one layer's pool (134,250,496 B at the cell's
    shapes), and no op materialises a layer's pool, copies the stack, or
    writes more than one block into it: the kernels read their operand
    straight from the stacked pool that the layer scan carries."""
    _assert_kernel(compiled)
    layer_pool = (CELL_N_BLOCKS, HKV, BLOCK, HD)
    layer_bytes = 2 * CELL_N_BLOCKS * HKV * BLOCK * HD
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    as_hlo = lambda dims: "bf16[" + ",".join(map(str, dims)) + "]"
    assert _pool_sized_moves(compiled.as_text(), as_hlo(layer_pool),
                             as_hlo((CFG.n_layers,) + layer_pool)) == []


def test_decode_paged_step_compiles_at_full_width(one_chip):
    """The decode step at the decode-batch cell's shapes (slicing each
    layer's pool out and back took 538,564,096 B of temporaries)."""
    _assert_reads_the_stack_in_place(_paged_step_compiled(one_chip,
                                                          "decode"))


def test_prefill_paged_chunk_compiles_at_full_width(one_chip):
    """One prefill chunk at the decode-batch cell's shapes (slicing each
    layer's pool out and back took 403,421,184 B of temporaries)."""
    _assert_reads_the_stack_in_place(_paged_step_compiled(one_chip,
                                                          "prefill"))


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
