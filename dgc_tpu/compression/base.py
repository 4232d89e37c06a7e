"""Compressor plugin boundary + baseline compressors.

This is the TPU-native survival of the reference's plugin boundary (the
north-star requirement): the vendored-Horovod ``Compressor`` interface and the
``Compression.{none,fp16}`` registry (/root/reference/dgc/horovod/compression.py:
22-77), plus the duck-typed ``communicate``/``synchronize`` dispatch the
reference patches into its distributed optimizer
(/root/reference/dgc/horovod/optimizer.py:39-40).

Here a compressor is a bundle of *pure functions* used inside the jitted train
step:

* ``compress(mem_state, name, grad, key) -> (payload, ctx, mem_state)``
* ``communicate(payload, ctx, axis_name, world_size) -> gathered``  (the
  collective: all_gather for sparse payloads, psum for dense)
* ``decompress(gathered, ctx, mem_state, world_size) -> (grad, mem_state)``

There is no ``synchronize`` step: the reference needs it because Horovod ops
are async handles drained at ``optimizer.step()``; under XLA the whole step is
one program, and its scheduler runs a collective beside compute where the
dataflow lets it (``training/step.py``).
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from dgc_tpu.compression.memory import Memory

__all__ = ["CompressCtx", "Compressor", "NoneCompressor", "FP16Compressor",
           "Compression"]


class CompressCtx(NamedTuple):
    """Static per-tensor context threaded from compress to decompress
    (the reference's ``ctx`` tuple, compression.py:166-174)."""
    name: Optional[str]
    numel: Optional[int]
    shape: Optional[Tuple[int, ...]]
    dtype: Any          # true (pre-wire) value dtype
    compressed: bool


class Compressor:
    """Interface: tensor-wise compression for gradient exchange
    (reference horovod/compression.py:22-39)."""

    #: memory plugin; the identity no-op by default
    memory: Memory = Memory()

    def initialize(self, named_params) -> None:
        """Precompute static per-tensor attributes (no-op for dense)."""

    def compress(self, mem_state, name, grad, key):
        raise NotImplementedError

    def communicate(self, payload, ctx: CompressCtx, axis_name: str,
                    world_size: int):
        raise NotImplementedError

    def decompress(self, gathered, ctx: CompressCtx, mem_state,
                   world_size: int):
        raise NotImplementedError


class _DenseCompressor(Compressor):
    """Shared dense path: payload is the whole gradient; the collective is a
    psum and decompress averages (hvd.Average semantics)."""

    def _wire(self, grad):
        return grad

    def _unwire(self, grad, dtype):
        return grad

    def make_flat_exchange(self, layout, plan=None):
        """Flat-path capability: the gradient buffer all-reduced, one psum
        per layout segment. ``plan`` is accepted for interface parity with
        the DGC engine and ignored — the dense exchange has exactly one
        regime."""
        from dgc_tpu.compression.flat import FlatDenseExchange
        return FlatDenseExchange(self, layout)

    def compress(self, mem_state, name, grad, key):
        ctx = CompressCtx(name=name, numel=grad.size, shape=grad.shape,
                          dtype=grad.dtype, compressed=False)
        return self._wire(grad), ctx, mem_state

    def communicate(self, payload, ctx, axis_name, world_size):
        return jax.lax.psum(payload, axis_name)

    def decompress(self, gathered, ctx, mem_state, world_size):
        out = self._unwire(gathered, ctx.dtype) / world_size
        return out.astype(ctx.dtype), mem_state


class NoneCompressor(_DenseCompressor):
    """Identity wire format (reference horovod/compression.py:42-53)."""


class FP16Compressor(_DenseCompressor):
    """fp16-on-the-wire compression for all floating-point gradients
    (reference horovod/compression.py:56-77). On TPU the psum itself runs in
    fp16, halving ICI traffic; the result is upcast before averaging."""

    def _wire(self, grad):
        if jnp.issubdtype(grad.dtype, jnp.floating):
            return grad.astype(jnp.float16)
        return grad

    def _unwire(self, grad, dtype):
        return grad.astype(dtype)


class Compression:
    """Registry of baseline compressors (reference horovod/compression.py:69-77)."""
    none = NoneCompressor
    fp16 = FP16Compressor
