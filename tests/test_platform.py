"""Where the program learns its backend: at call time, never at import.

Covers the seams that keep a missing chip from going unnoticed: imports
that touch no device, Pallas interpret mode on the CPU only, the
persistent compilation cache's fixed place, and ``chip_smoke.py``
refusing to run anywhere but on a TPU."""

import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.kernels import platform
from repro.kernels.fused_matmul.ops import fused_matmul

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, **kw):
    args = ([sys.executable, "-c", code_or_args]
            if isinstance(code_or_args, str) else code_or_args)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    return subprocess.run(args, capture_output=True, text=True, env=env,
                          timeout=300, **kw)


def test_import_initializes_no_backend():
    """Importing the compiler and its kernels must not bring up a
    backend: a process that does holds the chip."""
    out = _run(
        "import repro, repro.core, repro.kernels, repro.api\n"
        "import repro.core.selection, repro.core.passes.quantize\n"
        "import repro.kernels.fused_matmul.ops, repro.kernels.fast_act.ops\n"
        "import repro.kernels.decode_attention.ops\n"
        "from jax._src import xla_bridge\n"
        "print('INIT', xla_bridge.backends_are_initialized())\n")
    assert "INIT False" in out.stdout, out.stdout + out.stderr


@pytest.mark.parametrize("backend,expected",
                         [("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, expected):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert platform.on_tpu() == (backend == "tpu")
    if expected is None:
        with pytest.raises(RuntimeError, match="gpu"):
            platform.interpret_mode()
    else:
        assert platform.interpret_mode() is expected


def test_pallas_kernel_refuses_other_backend(monkeypatch):
    """A kernel asked for on a backend that is neither the CPU nor a
    TPU raises rather than quietly running interpreted."""
    x = np.ones((8, 16), np.float32)
    w = np.ones((16, 8), np.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="cannot run"):
        fused_matmul(x, w, use_pallas=True)


def test_selection_reads_backend_at_call_time(monkeypatch):
    """The int8 prior follows the backend of the compile, not of the
    import: off-TPU the lax lowering wins, on a TPU the q8 kernel."""
    from repro.core import ModelBuilder
    from repro.core.passes import run_pipeline
    from repro.core.selection import select_kernels

    mb = ModelBuilder().seed(3)
    h = mb.dense(mb.input((64,)), 32)
    g = mb.build([h]).copy()
    g.quant = {"mode": "int8", "calibrate": 1, "measure": False}
    g, _ = run_pipeline(g, ("quantize",))
    kernels = {}
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        sel = select_kernels(g, batch_size=8, target="pallas")
        kernels[backend] = [c.kernel for c in sel.values()]
    assert kernels == {"cpu": ["lax.dot"],
                       "tpu": ["pallas.fused_matmul_q8"]}


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_compilation_cache_dir_from_environment(monkeypatch,
                                                 restore_cache_dir, tmp_path):
    from repro.api.cache import use_compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compilation_cache_dir_fixed_in_checkout(monkeypatch,
                                                 restore_cache_dir):
    from repro.api.cache import use_compilation_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = use_compilation_cache()
    assert first == os.path.join(ROOT, ".jax_cache")
    assert use_compilation_cache() == first
    assert jax.config.jax_compilation_cache_dir == first


def test_compilation_cache_events_counted_inside_block_only():
    from repro.api.cache import compilation_cache_events
    with compilation_cache_events() as counts:
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event("/jax/some_other_event")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert counts == {"hits": 1, "misses": 2}


def test_chip_smoke_refuses_cpu():
    out = _run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
               cwd=ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "'cpu'" in out.stderr, out.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """Copied without the repository, the script has nothing to run."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run([sys.executable, "chip_smoke.py"], cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
