"""Distributed optimizer — the composition point generic over compressors.

TPU-native equivalent of the reference's patched Horovod
``_DistributedOptimizer`` (/root/reference/dgc/horovod/optimizer.py:105-194).
The reference registers per-parameter autograd hooks that launch async
collectives during backward and drains them in ``step()``; here the exchange
is ordinary dataflow inside the jitted step, and XLA's scheduler can run a
collective beside the remaining backward compute only where the program
lets it: one psum over the flat [P] buffer depends on every gradient and
ran with nothing beside it (9.71 ms of a 70.59 ms dense step on four v5e
chips; ledger, PR 27). Since PR 28 the dense exchange issues one collective
per layout segment in the order the backward pass finishes them, and the
step is compiled with XLA:TPU's asynchronous all-reduce on
(``training/step.py``): on the same chips every all-reduce but the last
4 MB ends under the backward pass (PERF.md section 6, PR 28). The DGC
engine's collectives are 0.09 ms there and are left as they were.

The plugin boundary survives intact (optimizer.py:39-40): for every gradient
the optimizer calls ``compressor.compress → communicate → decompress`` and is
otherwise generic over the compressor/memory pair. ``NoneCompressor`` yields
plain dense psum-averaging, ``DGCCompressor`` the sparse allgather path.

Payload fusion: with ``fuse_payloads=True`` (default) all sparse (values,
indices) payloads are concatenated into two arrays and exchanged with exactly
two ``all_gather`` calls per step instead of 2·T — the TPU answer to the
reference's per-tensor named-handle fusion and to its stated thresholding
overhead caveat (README.md:130-138).
"""

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import optax

from dgc_tpu.compression.base import Compressor
from dgc_tpu.telemetry import trace as _trace
from dgc_tpu.utils.pytree import named_flatten, named_unflatten

__all__ = ["DistributedOptimizer"]


class DistributedOptimizer:
    """Wraps a gradient transformation with compressed gradient exchange.

    Args:
      optimizer: base optax-style transformation (e.g. ``dgc_sgd``).
      compressor: the compression plugin (``DGCCompressor``,
        ``NoneCompressor``, ...). Its ``memory`` handles error feedback.
      axis_name: mesh axis over which gradients are exchanged (the
        host/DCN axis in two-tier mode).
      world_size: static TOTAL number of workers (across all axes).
      fuse_payloads: concatenate sparse payloads into one exchange.
      local_axis_name: set to enable the **two-tier hierarchical
        exchange** (the real form of the reference's "#Sparsified Nodes <
        #GPUs" regime, /root/reference/README.md:126-128,133-134): the
        gradient is first dense-aggregated over this mesh axis (intra-host
        ICI, near-free), then the sparse DGC exchange runs over
        ``axis_name`` only (cross-host DCN) among ``world_size //
        local_size`` sparsified nodes.
      local_size: workers per node on ``local_axis_name``; must divide
        ``world_size``.
    """

    #: True when the wrapped optimizer steps on LOCAL (pre-exchange)
    #: gradients and its state is therefore per-worker (Adasum scheme) —
    #: the train step then stores it with a leading [world] axis
    per_worker_opt_state = False

    def __init__(self, optimizer: optax.GradientTransformation,
                 compressor: Compressor, axis_name: str = "data",
                 world_size: int = 1, fuse_payloads: bool = True,
                 local_axis_name: Optional[str] = None,
                 local_size: int = 1):
        self.optimizer = optimizer
        self.compressor = compressor
        self.axis_name = axis_name
        self.world_size = world_size
        self.fuse_payloads = fuse_payloads
        if local_axis_name is not None:
            if local_size <= 1:
                raise ValueError(
                    "two-tier mode needs local_size > 1 (got "
                    f"{local_size}); omit local_axis_name for flat DP")
            if world_size % local_size:
                raise ValueError(
                    f"local_size {local_size} must divide world_size "
                    f"{world_size}")
        elif local_size > 1:
            raise ValueError(
                f"local_size {local_size} given without local_axis_name — "
                "name the mesh axis for the dense (ICI) tier to enable the "
                "two-tier exchange")
        self.local_axis_name = local_axis_name
        self.local_size = local_size if local_axis_name is not None else 1
        #: number of sparse-exchange participants on ``axis_name``
        #: (sparsified nodes in two-tier mode; all workers otherwise)
        self.num_nodes = world_size // self.local_size

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Mesh axes the data batch (and per-worker state) shards over —
        ``(axis_name,)`` flat, ``(axis_name, local_axis_name)`` two-tier."""
        if self.local_axis_name is not None:
            return (self.axis_name, self.local_axis_name)
        return (self.axis_name,)

    # ------------------------------------------------------------------ #

    def init(self, params) -> Any:
        return self.optimizer.init(params)

    def init_memory(self, params) -> Dict:
        named, _ = named_flatten(params)
        return self.compressor.memory.init(named.items())

    # ------------------------------------------------------------------ #
    # flat-buffer path (see dgc_tpu.compression.flat)                    #
    # ------------------------------------------------------------------ #

    def make_flat(self, params, plan=None):
        """Build the (ParamLayout, engine) pair for the fused flat-buffer
        pipeline. Compressed names are the compressor's initialized
        attributes (the dim>1 selection, reference train.py:136-140).
        Call again after ``warmup_compress_ratio`` changes the ratio.

        ``plan`` — optional per-bucket exchange plan
        (``compression.planner``); a ``Plan`` instance is re-fit to the
        rebuilt geometry via ``Plan.replan``, so warmup rebuilds keep the
        planner's fabric/cost context without the caller re-planning by
        hand."""
        from dgc_tpu.compression.flat import ParamLayout
        layout = ParamLayout.for_compressor(params, self.compressor)
        if plan is not None and hasattr(plan, "replan"):
            # re-fit to THIS layout's bucket geometry (ratio-dependent):
            # same fabric/cost/candidates, fresh payload sizes. A probe
            # engine supplies the buckets — host-side numpy bookkeeping
            # only, nothing is traced or compiled.
            probe = self.compressor.make_flat_exchange(layout)
            plan = plan.replan(probe)
        engine = self.compressor.make_flat_exchange(layout, plan=plan)
        return layout, engine

    def update_flat(self, flat_grads, opt_state, flat_params, mem_state,
                    key, engine, telemetry: bool = False,
                    health_out: Optional[Dict] = None,
                    send_frac=None,
                    grad_ready: Optional[Dict[str, int]] = None):
        """Flat-path analogue of :meth:`update`: fused exchange over the [P]
        buffer, then the wrapped optimizer on the same buffer.

        ``telemetry=True`` returns a fourth element — the engine's per-step
        stat pytree (``dgc_tpu.telemetry``); the default traces nothing
        extra. ``health_out`` forwards to the engine's exchange (payload-
        checksum mismatch counter, see ``resilience.integrity``);
        ``send_frac`` forwards this worker's adaptive send fraction
        (``resilience.adaptive``; None is Python-static off);
        ``grad_ready`` goes to an engine that ``takes_grad_ready`` (tensor
        name -> when its gradient is final, ``training/step.py``)."""
        exchanged, mem_state, tstats = self._exchange_flat(
            flat_grads, mem_state, key, engine, telemetry, health_out,
            send_frac, grad_ready)
        updates, opt_state = self._optimize(exchanged, opt_state,
                                            flat_params)
        return (updates, opt_state, mem_state) + tstats

    def _exchange_flat(self, flat_grads, mem_state, key, engine,
                       telemetry: bool, health_out, send_frac, grad_ready,
                       update=None):
        """The engine's exchange as the flat step calls it: ``(exchanged,
        memory, (tstats,) or ())``."""
        more = {} if grad_ready is None else {"grad_ready": grad_ready}
        if update is not None:
            more["update"] = update
        if telemetry:
            more["telemetry"] = True
        # parts of the step's ``update`` phase: the engine's own phases
        # nest inside ``exchange``, and what they leave is its glue. The
        # span times the engine's Python body while the step is traced;
        # the counts made inside stay ``step.trace``'s
        with _trace.phase("update", part="exchange"), _trace.span(
                "exchange.trace", owns_counts=False,
                engine=type(engine).__name__):
            exchanged, mem_state, *tstats = engine.exchange(
                flat_grads, mem_state, key, self.axis_name,
                self.num_nodes, local_axis=self.local_axis_name,
                local_size=self.local_size, health_out=health_out,
                send_frac=send_frac, **more)
        return exchanged, mem_state, tuple(tstats)

    def _optimize(self, exchanged, opt_state, params):
        with _trace.phase("update", part="optimizer"):
            return self.optimizer.update(exchanged, opt_state, params)

    def step_flat(self, flat_grads, opt_state, flat_params, mem_state,
                  key, engine, telemetry: bool = False,
                  health_out: Optional[Dict] = None, send_frac=None,
                  grad_ready: Optional[Dict[str, int]] = None,
                  in_place: bool = True):
        """:meth:`update_flat` and the parameter add: ``(new_params,
        opt_state, mem_state[, tstats])``.

        Where the optimizer offers its elementwise rule (``optim/sgd.py::
        ElementwiseRule``) and the engine's apply pass streams the
        buffer, the two are ONE pass: the engine updates the compressed
        block [0, T) of the parameters and the momentum buffer where it
        makes their gradient (``flat.InPlaceUpdate``), and what is left
        here is the rule over the dense tail [T, P), written into the
        same buffers in place. ``in_place=False`` (a step that still
        needs the buffers it came with: no donation, the guards' atomic
        skip) makes no offer; an offer that is not taken leaves the
        program :meth:`update_flat` and an add compile."""
        rule = getattr(self.optimizer, "rule", None)
        offer = None
        if (rule is not None and in_place and not self.per_worker_opt_state
                and getattr(engine, "takes_update", False)):
            from dgc_tpu.compression.flat import InPlaceUpdate
            offer = InPlaceUpdate(
                rule.blocks(opt_state, flat_params), rule.step,
                functools.cache(lambda: rule.scalars(opt_state)))
        if offer is None:
            # through the overridable entry (Adasum's own update_flat)
            more = {} if grad_ready is None else {"grad_ready": grad_ready}
            updates, opt_state, mem_state, *tstats = self.update_flat(
                flat_grads, opt_state, flat_params, mem_state, key, engine,
                telemetry=telemetry, health_out=health_out,
                send_frac=send_frac, **more)
        else:
            exchanged, mem_state, tstats = self._exchange_flat(
                flat_grads, mem_state, key, engine, telemetry, health_out,
                send_frac, grad_ready, update=offer)
            if offer.taken:  # dgclint: ok[tracer-branch] — a Python bool the engine sets while it is traced
                new_params, opt_state = self._update_tail(
                    rule, offer, exchanged, opt_state, engine)
                return (new_params, opt_state, mem_state, *tstats)
            updates, opt_state = self._optimize(exchanged, opt_state,
                                                flat_params)
        return (self._add(flat_params, updates), opt_state, mem_state,
                *tstats)

    @staticmethod
    def _update_tail(rule, offer, tail_grad, opt_state, engine):
        """After an offer was taken: the same rule over the dense tail
        [T, P), read from and written into the buffers the pass left (the
        tail keeps the XLA optimizer), and the optimizer's next state."""
        T = engine.T
        blocks = offer.state
        with _trace.phase("update", part="optimizer"):
            if tail_grad.shape[0]:
                idx = T + jax.lax.iota(engine.layout.index_dtype,
                                       tail_grad.shape[0])
                tail = rule.step(tail_grad, idx, offer.scalars(),
                                 *(b[T:] for b in blocks))
                blocks = tuple(jax.lax.dynamic_update_slice(b, t, (T,))
                               for b, t in zip(blocks, tail))
            return blocks[0], rule.advance(opt_state, blocks)

    @staticmethod
    def _add(params, updates):
        # the add is the root of the optimizer's fusion, and a fusion
        # carries its root's scope: without it the part reads nothing
        with _trace.phase("update", part="optimizer"):
            return params + updates

    # ------------------------------------------------------------------ #

    def exchange(self, grads, mem_state, key: Optional[jax.Array]
                 ) -> Tuple[Any, Dict]:
        """Compress + communicate + decompress every gradient leaf.

        ``grads`` is a (nested) pytree; returns the exchanged pytree of the
        same structure plus the updated memory state.

        In two-tier mode the gradients are first dense-averaged over the
        local (ICI) axis; the compress/communicate/decompress pipeline then
        runs among the ``num_nodes`` sparsified nodes on ``axis_name``
        exactly as in flat DP.
        """
        if self.local_axis_name is not None:
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g, self.local_axis_name)
                / self.local_size, grads)
        named, treedef = named_flatten(grads)
        comp = self.compressor

        compressed = {}       # name -> (payload, ctx)
        dense = {}            # name -> (payload, ctx)
        for i, (name, g) in enumerate(named.items()):
            k = jax.random.fold_in(key, i) if key is not None else None
            payload, ctx, mem_state = comp.compress(mem_state, name, g, k)
            (compressed if ctx.compressed else dense)[name] = (payload, ctx)

        out: Dict[str, jax.Array] = {}

        # --- dense fallback path: psum + average (+ memory correction) ---
        for name, (payload, ctx) in dense.items():
            gathered = comp.communicate(payload, ctx, self.axis_name,
                                        self.num_nodes)
            out[name], mem_state = comp.decompress(gathered, ctx, mem_state,
                                                   self.num_nodes)

        # --- sparse path --- (fusion is a compressor capability discovered
        # by duck typing, like the reference's communicate/synchronize
        # dispatch, optimizer.py:39-40)
        if compressed:
            fused = getattr(comp, "exchange_fused", None)
            if self.fuse_payloads and fused is not None and len(compressed) > 1:
                fused_out, mem_state = fused(compressed, self.axis_name,
                                             self.num_nodes, mem_state)
                out.update(fused_out)
            else:
                for name, (payload, ctx) in compressed.items():
                    gathered = comp.communicate(payload, ctx, self.axis_name,
                                                self.num_nodes)
                    out[name], mem_state = comp.decompress(
                        gathered, ctx, mem_state, self.num_nodes)

        ordered = {name: out[name] for name in named}
        return named_unflatten(ordered, treedef), mem_state

    # ------------------------------------------------------------------ #

    def update(self, grads, opt_state, params, mem_state,
               key: Optional[jax.Array] = None):
        """Full distributed update: exchange, then the wrapped optimizer
        (the reference's ``step()`` = synchronize + base step,
        optimizer.py:176-187)."""
        with _trace.phase("update", part="exchange"):
            exchanged, mem_state = self.exchange(grads, mem_state, key)
        with _trace.phase("update", part="optimizer"):
            updates, opt_state = self.optimizer.update(exchanged, opt_state,
                                                       params)
        return updates, opt_state, mem_state
