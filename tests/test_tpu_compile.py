"""The served path compiled for a described TPU v5e.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse -- a block shape off the (8, 128) tiling, a kernel over its fast
memory, a program over the device's memory.  Shapes are TinyLlama-1.1B's
published widths in bf16 (H 32, Hkv 4, head_dim 64, page 128), and for
the step programs InternLM2-1.8B's too (H 16, Hkv 8, head_dim 128); the
whole decode, mixed and chunk-wave programs are compiled from
``jax.eval_shape`` shapes, with checks that no program holds a second
pool or moves a layer of it.
"""
import re

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
from repro.serving.executor import PagedExecutor

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


def _check_in_place(compiled, pool_bytes=None):
    """Both pools donated and updated in place: aliased, and no second
    pool among the program's temporaries."""
    pool_bytes = pool_bytes or _pool_bytes()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes


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


# Pool sizes of the step programs' checks.  Both have a factor 7, which
# no width of either model has, so a buffer of a whole number of pool
# layers can only have come from the pool.
POOL_PAGES = (224, 448)
STEP_WIDTHS = ("internlm2-1.8b", "skymemory-tinyllama")
_MOVES = re.compile(r"\s*(?:ROOT )?%(\S+) = (.+?) ([\w-]+)\(")
_ARRAY = re.compile(r"[a-z]+\d*\[([\d,]*)\]")


def _layer_moves(hlo: str, layer_elems: int) -> list[str]:
    """Instructions, fused or not, named for a copy, transpose or
    dynamic-slice whose result holds a whole number of pool layers, in
    any layout."""
    found = []
    for line in hlo.splitlines():
        m = _MOVES.match(line)
        if not m or not re.search(r"copy|transpose|dynamic-slice",
                                  m.group(1) + " " + m.group(3)):
            continue
        for dims in _ARRAY.findall(m.group(2)):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            if n >= layer_elems and n % layer_elems == 0:
                found.append(m.group(1))
    return found


@pytest.fixture(scope="module")
def step_models(one_chip):
    """Per width: the model, its parameters' shapes and the executor whose
    step programs are compiled (built once, on first use)."""
    built = {}

    def get(name):
        if name not in built:
            cfg = get_config(name).replace(dtype="bfloat16")
            model = Model(cfg)
            params = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip),
                jax.eval_shape(model.init, jax.random.PRNGKey(0)))
            ex = PagedExecutor(model, params, _FreeListPool(),
                               chunk_tokens=CHUNK, max_seq_len=MAX_SEQ)
            built[name] = cfg, params, ex
        return built[name]

    return get


class _FreeListPool:
    contiguous = False


def _lower_step(one_chip, cfg, params, ex, program, pages):
    pool = _specs(one_chip, ((cfg.num_layers, pages, PAGE, cfg.num_kv_heads,
                              cfg.head_dim), BF16))[0]
    i32 = lambda *s: _specs(one_chip, (s, jnp.int32))[0]      # noqa: E731
    f32 = lambda *s: _specs(one_chip, (s, jnp.float32))[0]    # noqa: E731
    dec = (params, pool, pool, i32(BATCH, P), i32(BATCH), i32(BATCH),
           _specs(one_chip, ((2,), jnp.uint32))[0], f32(BATCH), i32(BATCH),
           f32(BATCH))
    if program == "decode":
        return ex._step.lower(*dec, mode="greedy")
    if program == "mixed":
        return ex._mixed.lower(*dec, i32(1, CHUNK), i32(1, P), i32(1),
                               i32(1), f32(1), i32(1), f32(1), mode="greedy")
    return ex._chunk_wave.lower(params, pool, pool, i32(BATCH, CHUNK),
                                i32(BATCH, P), i32(BATCH), i32(BATCH))


@pytest.mark.parametrize("width", STEP_WIDTHS)
@pytest.mark.parametrize("program", ["decode", "mixed", "chunk_wave"])
def test_step_reads_pool_in_place(one_chip, step_models, compiled_kernels,
                                  program, width):
    """The decode step, the mixed step at the largest chunk buffer and the
    chunk wave read and write the carried pools in place: no instruction
    slices, copies or transposes a layer of a pool (or the whole pool),
    and the temporaries do not grow with the pool."""
    cfg, params, ex = step_models(width)
    page_elems = PAGE * cfg.num_kv_heads * cfg.head_dim
    temps = []
    for pages in POOL_PAGES:
        compiled = _lower_step(one_chip, cfg, params, ex, program,
                               pages).compile()
        assert "tpu_custom_call" in compiled.as_text()
        assert _layer_moves(compiled.as_text(), pages * page_elems) == []
        pool_bytes = cfg.num_layers * pages * page_elems * 2
        _check_in_place(compiled, pool_bytes)
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
    page_bytes = 2 * cfg.num_layers * page_elems * 2     # both pools
    assert abs(temps[1] - temps[0]) < page_bytes
