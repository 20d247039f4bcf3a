"""The system under test, built from a configuration file.

This is the only module of the benchmark that imports the serving program
(``repro``, from the checkout's ``src``).  It maps a configuration file
onto the program's ``ModelConfig``, checks that the benchmark's weights
have the program's layout, sizes the page pool from the HBM the device
reports free, and builds the ``EngineCluster`` over the simulated
constellation that every cell serves through.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.core import (  # noqa: E402
    ConstellationKVC,
    ConstellationSpec,
    IslTransport,
    LosWindow,
    Sat,
    SimClock,
    Strategy,
)
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving import EngineCluster, Request, SamplingParams  # noqa: E402,F401


def model_config(cfg: dict) -> ModelConfig:
    """The program's configuration for a dense GQA decoder file."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim") or d // h, mlp_type="swiglu",
        norm_type="rmsnorm", norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"], source=cfg["source"])


def check_layout(model: Model, params) -> None:
    """The benchmark's weights must have exactly the program's tree,
    shapes and dtypes."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the benchmark's weights do not match the "
                           "program's parameter layout")


def page_bytes(cfg: ModelConfig, block: int) -> int:
    item = jax.numpy.dtype(cfg.kvc_dtype or cfg.dtype).itemsize
    return 2 * cfg.num_layers * block * cfg.num_kv_heads * cfg.head_dim * item


class _Pool:
    """The pool as the executor sees it when programs are only lowered."""

    contiguous = False


def wave_rows(max_batch: int) -> list[int]:
    """Row counts the scheduler pads a chunk wave to: powers of two up to
    ``max_batch``, and the next one where ``max_batch`` is not one."""
    rows = [1 << i for i in range(max_batch.bit_length())
            if 1 << i <= max_batch]
    if rows[-1] < max_batch:
        rows.append(rows[-1] * 2)
    return rows


def step_programs(model, params, dep: dict, pages: int, spec=None,
                  every_shape: bool = False) -> dict:
    """The executor's step programs lowered at a pool of ``pages`` pages,
    as ``{label: thunk returning the lowered program}``: the decode step
    at ``max_batch``, the mixed step at the largest chunk buffer and the
    chunk wave at one row of the smallest buffer and at ``max_batch``
    rows of the largest; with ``every_shape``, the mixed step at every
    buffer and the chunk wave at every row count and buffer the
    scheduler launches.  ``params`` may be arrays or shapes;
    ``spec(shape, dtype)`` makes an argument's shape (default: on the
    default device)."""
    import jax.numpy as jnp

    from repro.serving.executor import PagedExecutor

    spec = spec or jax.ShapeDtypeStruct
    cfg = model.cfg
    params = jax.tree.map(lambda a: spec(a.shape, a.dtype), params)
    b, c_max = dep["max_batch"], dep["chunk_tokens"]
    p = dep["max_seq_len"] // dep["block"]
    pool = spec((cfg.num_layers, pages, dep["block"], cfg.num_kv_heads,
                 cfg.head_dim), jnp.dtype(cfg.kvc_dtype or cfg.dtype))
    ex = PagedExecutor(model, params, _Pool(), chunk_tokens=c_max,
                       max_seq_len=dep["max_seq_len"])
    i32 = lambda *s: spec(s, jnp.int32)            # noqa: E731
    f32 = lambda *s: spec(s, jnp.float32)          # noqa: E731
    dec = (params, pool, pool, i32(b, p), i32(b), i32(b), spec((2,),
           jnp.uint32), f32(b), i32(b), f32(b))
    progs = {"decode": lambda: ex._step.lower(*dec, mode="greedy")}
    bufs = sorted({ex.chunk_buf(v) for v in range(1, c_max + 1)})
    for c in bufs if every_shape else bufs[-1:]:
        chunk = (i32(1, c), i32(1, p), i32(1), i32(1), f32(1), i32(1),
                 f32(1))
        progs[f"mixed[{c}]"] = (lambda chunk=chunk: ex._mixed.lower(
            *dec, *chunk, mode="greedy"))
    waves = ([(r, c) for r in wave_rows(b) for c in bufs] if every_shape
             else [(1, bufs[0]), (b, bufs[-1])])
    for r, c in waves:
        progs[f"chunk_wave[{r}x{c}]"] = (
            lambda r=r, c=c: ex._chunk_wave.lower(
                params, pool, pool, i32(r, c), i32(r, p), i32(r), i32(r)))
    return progs


def compile_all(progs: dict, workers: int | None = None) -> dict:
    """Compile lowered programs side by side (the compiler releases the
    interpreter's lock), each into the persistent compile cache; returns
    ``{label: memory_analysis}``.  Lowering stays on the calling
    thread."""
    from concurrent.futures import ThreadPoolExecutor

    lowered = {k: lower() for k, lower in progs.items()}
    workers = workers or max(1, min(8, (os.cpu_count() or 2) - 2))
    with ThreadPoolExecutor(workers) as pool:
        done = pool.map(lambda lo: lo.compile().memory_analysis(),
                        lowered.values())
        return dict(zip(lowered, done))


def step_temp_bytes(model, params, dep: dict, pages: int) -> int:
    """The most temporary memory any step program takes at a pool of
    ``pages`` pages, by the compiler's own ``memory_analysis``."""
    return max(m.temp_size_in_bytes for m in compile_all(
        step_programs(model, params, dep, pages)).values())


# two pool sizes, in pages, at which the step programs' temporaries are
# measured; the pool's share of them is the slope between the two
PROBE_PAGES = (64, 128)


def pool_pages(model, params, dep: dict) -> tuple[int, dict | None]:
    """Pages of the pool: the HBM free after the parameters, less what
    the step programs take beside the pool, less what the eager
    write-back forward stages (one longest sequence's K/V payload in and
    one out), and less the restored prefixes that wait on the device,
    page-shaped, until their chunk imports them.  A sequence is admitted
    only with pool pages for its whole prompt, so those wait for at most
    one page per pool page: each page is charged twice, with the part of
    the step programs' temporaries that grows with the pool (a copy of
    it, where the compiler makes one).  That part is measured by
    compiling the step programs at two small pool sizes.  Returns
    ``(pages, sizes)`` with the measured sizes in bytes; a device that
    reports no memory (the CPU) gets ``dep["cpu_pages"]`` and ``None``."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return dep["cpu_pages"], None
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    per_page = page_bytes(model.cfg, dep["block"])
    (p1, p2), (t1, t2) = PROBE_PAGES, [
        step_temp_bytes(model, params, dep, n) for n in PROBE_PAGES]
    temp_per_page = max(0.0, (t2 - t1) / (p2 - p1))
    temp_fixed = t1 - temp_per_page * p1
    write_back = 2 * (dep["max_seq_len"] // dep["block"]) * per_page
    pages = int((free - temp_fixed - write_back)
                // (2 * per_page + temp_per_page))
    return pages, {"free": int(free), "temp_fixed": int(temp_fixed),
                   "temp_per_page": int(temp_per_page),
                   "write_back": int(write_back),
                   "restore_per_page": int(per_page)}


def build_cluster(model: Model, params, dep: dict, pages: int, seed: int):
    """One replica over the paper's 19x5 constellation (550 km,
    ROTATION_HOP, 10 chunk servers), its fabric clock at rate 1 so that
    modelled ISL flights are waited in real time.  No rotation."""
    spec = ConstellationSpec(num_planes=5, sats_per_plane=19,
                             altitude_km=550.0)
    clock = SimClock(rate=1.0)
    kvc = ConstellationKVC(
        spec, LosWindow(Sat(2, 9), 5, 5), Strategy.ROTATION_HOP,
        num_servers=10, chunk_bytes=dep["chunk_bytes"],
        per_sat_capacity_bytes=dep["per_sat_capacity_bytes"],
        transport=IslTransport(spec, clock=clock,
                               chunk_processing_time_s=2e-4,
                               probe_timeout_s=5e-3),
    )
    return EngineCluster(
        model, params, kvc, num_replicas=1, block_size=dep["block"],
        max_seq_len=dep["max_seq_len"], max_batch=dep["max_batch"],
        num_pages=pages, chunk_tokens=dep["chunk_tokens"],
        rotate_every_s=None, seed=seed % (2 ** 31))


def request(spec) -> Request:
    return Request(prompt=spec.prompt, sampling=SamplingParams(
        temperature=0.0, max_new_tokens=spec.max_new_tokens))


def tokenize(cluster, text: str) -> list[int]:
    return cluster.tokenizer.encode(text)
