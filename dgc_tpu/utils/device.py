"""The one place that decides whether a program may run off the chip.

Every on-chip entry point (``train.py`` without ``--cpu_mesh`` /
``--devices cpu``, ``bench.py``, ``chip_smoke.py``, the timing and
profiling scripts) calls :func:`require_tpu` before it builds anything:
a timing, a trace or a "trained OK" taken on XLA's CPU backend says
nothing about the system, so it must not be printable at all.
"""

import jax

__all__ = ["require_tpu"]


def require_tpu(who: str) -> None:
    """Exit non-zero, naming the backend, unless JAX's default backend
    is a TPU."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"{who}: needs a TPU backend, but jax.default_backend() is "
            f"{backend!r} — run it on the chip; there is no CPU fallback")
