"""The served path compiled for a described TPU v5e at TinyLlama widths.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse -- a block shape off the (8, 128) tiling, a kernel over its fast
memory, a program over the device's memory.  Shapes are TinyLlama-1.1B's
published widths in bf16 (H 32, Hkv 4, head_dim 64, page 128); the whole
decode and chunk-wave programs are compiled from ``jax.eval_shape``
shapes, with a check that no program holds a second pool.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.chunked_prefill import (
    chunked_prefill_attention,
    chunked_prefill_paged,
)
from repro.kernels.paged_attention import paged_attention
from repro.models import cache as cache_lib
from repro.models.model import Model

CFG = get_config("skymemory-tinyllama")
H, HKV, D = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
PAGE, MAX_SEQ, BATCH, CHUNK = 128, 2048, 8, 256
P = MAX_SEQ // PAGE
N_PAGES = 2048                  # pool pages: 2.75 GiB per pool at 22 layers
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _specs(one_chip, *shapes):
    return [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]


KERNELS = {
    "paged_attention[block_table]": (
        lambda q, k, v, lens, bt: paged_attention(
            q, k, v, lens, block_tables=bt),
        [((BATCH, H, D), BF16), ((N_PAGES, PAGE, HKV, D), BF16),
         ((N_PAGES, PAGE, HKV, D), BF16), ((BATCH,), jnp.int32),
         ((BATCH, P), jnp.int32)]),
    "paged_attention[contiguous]": (
        lambda q, k, v, lens: paged_attention(q, k, v, lens),
        [((BATCH, H, D), BF16), ((BATCH, P, PAGE, HKV, D), BF16),
         ((BATCH, P, PAGE, HKV, D), BF16), ((BATCH,), jnp.int32)]),
    "chunked_prefill_paged[chunk]": (
        chunked_prefill_paged,
        [((2, CHUNK, H, D), BF16), ((N_PAGES, PAGE, HKV, D), BF16),
         ((N_PAGES, PAGE, HKV, D), BF16), ((2,), jnp.int32),
         ((2, P), jnp.int32), ((2,), jnp.int32)]),
    "chunked_prefill_paged[replay]": (
        chunked_prefill_paged,
        [((1, 1, H, D), BF16), ((N_PAGES, PAGE, HKV, D), BF16),
         ((N_PAGES, PAGE, HKV, D), BF16), ((1,), jnp.int32),
         ((1, P), jnp.int32), ((1,), jnp.int32)]),
    "chunked_prefill_attention": (
        lambda q, k, v: chunked_prefill_attention(q, k, v, q_offset=512),
        [((1, PAGE, H, D), BF16), ((1, 640, HKV, D), BF16),
         ((1, 640, HKV, D), BF16)]),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = jax.jit(fn).lower(*_specs(one_chip, *shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _pool_specs(one_chip):
    shape = (CFG.num_layers, N_PAGES, PAGE, HKV, D)
    return _specs(one_chip, (shape, BF16), (shape, BF16))


def _pool_bytes():
    return CFG.num_layers * N_PAGES * PAGE * HKV * D * 2


def _check_in_place(compiled):
    """Both pools donated and updated in place: aliased, and no second
    pool among the program's temporaries."""
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * _pool_bytes()
    assert mem.temp_size_in_bytes < _pool_bytes()


@pytest.fixture(scope="module")
def tinyllama(one_chip):
    model = Model(CFG)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    return model, params


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The model code picks Pallas only where JAX's backend is a TPU; here
    the test steers it onto the compiled kernels."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    monkeypatch.setattr(ops, "_interpret", lambda: False)


@pytest.mark.parametrize("program", ["decode", "chunk_wave"])
def test_full_width_step_compiles_in_place(one_chip, tinyllama,
                                           compiled_kernels, program):
    model, params = tinyllama
    if program == "decode":
        fn = model.decode_step_paged
        args = _specs(one_chip, ((BATCH, 1), jnp.int32),
                      ((BATCH, P), jnp.int32), ((BATCH,), jnp.int32))
    else:
        fn = model.prefill_chunk_paged
        args = _specs(one_chip, ((4, CHUNK), jnp.int32), ((4, P), jnp.int32),
                      ((4,), jnp.int32), ((4,), jnp.int32))
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, *_pool_specs(one_chip), *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _check_in_place(compiled)


def test_page_restore_compiles_in_place(one_chip):
    """``PagedKVCache.write_pages``' program: restored pages are
    scattered into the donated pools, with no second pool."""
    blocks = (CFG.num_layers, 8, PAGE, HKV, D)
    args = _specs(one_chip, ((8,), jnp.int32), (blocks, BF16),
                  (blocks, BF16))
    compiled = cache_lib._put_pages.lower(
        *_pool_specs(one_chip), *args).compile()
    _check_in_place(compiled)
