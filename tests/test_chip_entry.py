"""The refusals that keep a chipless run from passing for a chip run, and
the one rule for where the compile cache lives. All cheap: no model is
compiled."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_on_cpu_backend(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)


def test_train_refuses_cpu_backend_unless_asked():
    r = _run_on_cpu_backend(
        "train.py", "--configs", "configs/cifar/resnet20.py",
        "configs/dgc/wm0.py")
    assert r.returncode != 0
    assert "jax.default_backend() is 'cpu'" in r.stderr
    assert "--cpu_mesh" in r.stderr
    assert "==> creating" not in r.stdout     # refused before building


def test_chip_smoke_refuses_cpu_backend():
    r = _run_on_cpu_backend("chip_smoke.py")
    assert r.returncode != 0
    assert "jax.default_backend() is 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout             # no result line


@pytest.fixture
def cache_config():
    """Restore the process-wide cache setting after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_placed_from_outside(monkeypatch, cache_config):
    from dgc_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/some/dir")
    assert compile_cache.enable() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before   # left alone

    monkeypatch.delenv(compile_cache.CACHE_ENV)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_kernel_flag_declined_for_non_geometry_raises_on_tpu(monkeypatch):
    """Off the chip a flag the engine cannot honour falls through to the
    XLA path (the parity tests rely on it); on the TPU backend it must
    raise."""
    from dgc_tpu import DGCCompressor, DGCSGDMemory
    from dgc_tpu.compression.flat import FlatDGCEngine, ParamLayout
    from dgc_tpu.ops import kernels

    params = {"w": jnp.zeros((64, 512)), "b": jnp.zeros((512,))}

    def engine(**flags):
        comp = DGCCompressor(0.01, memory=DGCSGDMemory(
            momentum=0.9, dtype="bfloat16"), **flags)
        comp.initialize([("w", params["w"])])
        return FlatDGCEngine(comp, ParamLayout.for_compressor(params, comp))

    eng = engine(megakernel=True, fused_apply=True)     # CPU: falls through
    assert eng._mk_fwd_ids == ()
    assert not eng._use_fused_apply(eng._mem, True, jnp.float32)

    monkeypatch.setattr(kernels, "use_pallas", lambda: True)
    with pytest.raises(ValueError, match=r"megakernel=True\) cannot"):
        engine(megakernel=True)
    eng = engine(fused_apply=True)
    with pytest.raises(ValueError, match=r"fused_apply=True\) cannot"):
        eng._use_fused_apply(eng._mem, True, jnp.float32)   # int8 EF wire
    # honourable flags build as before
    assert engine(fused_select=True).buckets
