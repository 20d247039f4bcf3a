"""``chip_smoke.py`` on the CPU: its refusals, and every phase after the
device check at smoke width with interpret-mode kernels.

This guards the script's control flow and its last-line format on every
change; only a TPU run shows what the compiled kernels do.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config, smoke_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # its dataclass resolves annotations
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        del sys.modules["chip_smoke"]


def test_device_check_refuses_cpu(smoke, monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.check_device()


def test_device_check_refuses_kernel_override(smoke, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "jnp")
    with pytest.raises(smoke.SmokeFailure, match="REPRO_KERNEL_IMPL"):
        smoke.check_device()


def test_script_on_cpu_exits_nonzero_without_ok_line():
    env = {k: v for k, v in os.environ.items() if k != "REPRO_KERNEL_IMPL"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


_CACHE_PROBE = """
import sys
from pathlib import Path
import jax, jax.numpy as jnp
from repro import jit_cache
jit_cache.CHECKOUT = Path(sys.argv[1])
print(jit_cache.enable_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_lands_in_one_fixed_place(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's
    ``.jax_cache``; nothing under the temp directory either way."""
    tmp = tmp_path / "tmp"
    tmp.mkdir()
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "TPU_LOG_DIR")}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp),
               PYTHONPATH=str(ROOT / "src"))
    want = tmp_path / "checkout" / ".jax_cache"
    if from_env:
        want = tmp_path / "given"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, str(tmp_path / "checkout")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split()[-1] == str(want)
    assert any(want.iterdir())
    assert not any(tmp.iterdir())


def test_phases_on_cpu_end_with_ok_line(smoke, monkeypatch, capsys):
    """Kernels vs oracle, a 2-replica closed batch with constellation
    prefix hits, served tokens and logits vs the jnp oracle, and an open
    stream -- the chip run's phases, in interpret mode at smoke width."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    cfg = smoke_config(get_config("skymemory-tinyllama")).replace(
        num_kv_heads=2)
    size = smoke.Size(
        cfg=cfg, block_size=16, max_seq_len=256, max_batch=4,
        num_pages=48, chunk_bytes=6 * 1024, requests=4, doc_blocks=3,
        max_new_tokens=4, arrivals=6, stream_new_tokens=4)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    smoke.run(size, device, compiled=False)
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    kernels = [ln for ln in lines if ln.startswith("[kernels]")]
    assert len(kernels) == 5 and all(ln.endswith(" ok") for ln in kernels)
    served = [ln for ln in lines if ln.startswith("[served] request")]
    assert len(served) == size.requests
    assert all(ln.endswith(" ok") for ln in served)
    logits = [ln for ln in lines if ln.startswith("[logits]")]
    assert len(logits) == 3 and all(ln.endswith(" ok") for ln in logits)
    assert any(ln.startswith("[closed] constellation: block_hits=")
               for ln in lines)
    assert any(ln.startswith("[stream]") for ln in lines)
    assert any(ln.startswith("[info] one run of chip_smoke.py")
               for ln in lines)
