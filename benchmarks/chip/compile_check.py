"""Compile the cells' step programs for a described TPU v5e, no chip needed.

    JAX_PLATFORMS=cpu python benchmarks/chip/compile_check.py [config ...]

For each configuration (default: every file in ``configs/``): the
executor's decode step at ``max_batch``, its mixed step at every chunk
buffer, its chunk wave at every row count and buffer,
and the reference's forward pass, each compiled by the TPU compiler for
one chip of a described ``v5e:2x2`` at the pool size a run would take.
Prints each program's ``memory_analysis`` and whether a Pallas kernel
(``tpu_custom_call``) is in it.  A program the chip would refuse raises.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_KERNEL_IMPL"] = "pallas"
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import program  # noqa: E402
import reference  # noqa: E402

# the HBM a v5e lets a process use
HBM_LIMIT = 15.75 * 2 ** 30


def main(names: list[str]) -> None:
    from jax.experimental import topologies

    from repro.kernels import ops

    ops._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    def temp(progs) -> dict:
        return {k: lower().compile().memory_analysis().temp_size_in_bytes
                for k, lower in progs.items()}

    for name in names:
        cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
        dep = cfg["deployment"]
        model = program.Model(program.model_config(cfg))
        params = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                              reference.param_shapes(cfg))
        p_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(params))
        # program.pool_pages' sizing, with the weights as the only tenant
        (p1, p2) = program.PROBE_PAGES
        t1, t2 = (max(temp(program.step_programs(
            model, params, dep, n, spec)).values()) for n in (p1, p2))
        per_page = program.page_bytes(model.cfg, dep["block"])
        slope = max(0.0, (t2 - t1) / (p2 - p1))
        write_back = 2 * (dep["max_seq_len"] // dep["block"]) * per_page
        pages = int((HBM_LIMIT - p_bytes - (t1 - slope * p1) - write_back)
                    // (2 * per_page + slope))
        progs = program.step_programs(model, params, dep, pages, spec,
                                      every_shape=True)
        items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str))))
        progs["reference"] = lambda: reference._logits_rows.lower(
            params, spec((dep["max_seq_len"],), jnp.int32),
            spec((), jnp.int32), cfg_items=items, rows=dep["check_rows"],
            fp8=False)
        print(f"{name}: {pages} pool pages ({slope / per_page!r} pool "
              f"copies among the temporaries), weights {p_bytes} bytes",
              flush=True)
        for label, lower in progs.items():
            t = time.perf_counter()
            try:
                compiled = lower().compile()
            except jax.errors.JaxRuntimeError as e:
                print(f"  {label}: REFUSED: {str(e).splitlines()[0]}",
                      flush=True)
                continue
            m = compiled.memory_analysis()
            kernels = compiled.as_text().count("tpu_custom_call")
            print(f"  {label}: compiled in {time.perf_counter() - t:.1f} s; "
                  f"args {m.argument_size_in_bytes} out "
                  f"{m.output_size_in_bytes} alias {m.alias_size_in_bytes} "
                  f"temp {m.temp_size_in_bytes}; tpu_custom_call {kernels}",
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or sorted(p.stem for p in (HERE / "configs").glob(
        "*.json")))
