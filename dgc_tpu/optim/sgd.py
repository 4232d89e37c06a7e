"""SGD optimizers as optax-style gradient transformations.

``dgc_sgd`` replicates the reference's ``DGCSGD`` (/root/reference/dgc/optim/
sgd.py:30-70) — the critical *optimizer split* (SURVEY.md §2.9): gradient
momentum is applied **pre-compression** inside the DGC memory, so the optimizer
must NOT re-apply momentum to the gradient. It applies momentum + nesterov only
to the weight-decay term: ``d_p = wd·p`` runs through the momentum buffer, then
the (already momentum-corrected, decompressed) gradient is added raw, and the
parameter moves by ``-lr · d_p``.

``sgd`` replicates stock ``torch.optim.SGD`` (momentum buffer over
``grad + wd·p``) for the dense/no-DGC baseline, so compressed and dense runs
differ only in the gradient path.

Both take ``lr`` as a float or a ``step -> lr`` schedule (the harness drives
per-step warm-up through it, SURVEY.md §2.10) and an optional
``weight_decay_mask`` marking which parameters receive weight decay (the
reference's ``optimize_bn_separately`` puts BN params in a wd=0 group,
train.py:121-125): a pytree, or a callable ``params -> pytree`` evaluated
inside the trace. A mask leaf is one of

* a Python bool — a whole-tensor group, like the reference's param groups;
  the wd=0 branch is then dropped at trace time;
* a boolean or 0/1 array built **inside** the trace — the flat-buffer
  path, where every parameter lives in one [P] array and the BN split is
  per coordinate. ``ParamLayout.mask_vector`` returns such a callable
  (``flat.LayoutMask``): it builds the mask from an iota and a few range
  compares, which fuse into the update, so the optimizer's one HBM-bound
  fusion streams p, buf and g and nothing else;
* a [P] array closed over by the step — supported, and the slow form: it
  is a compile-time constant, XLA folds every expression below that uses
  it (``wd * m``, ``m``, ``1 - m``) into a [P] constant of its own, and
  the fusion streams each of them from HBM beside p, buf and g (three in
  ``dgc_sgd``, one in ``sgd``: +0.43 and +0.13 ms per step at ResNet-50
  on a v5e, PERF.md §6, PR 25).
"""

from typing import Any, Callable, NamedTuple, Union

import jax
import jax.numpy as jnp
import optax

__all__ = ["dgc_sgd", "sgd", "SGDState", "ElementwiseRule"]

ScalarOrSchedule = Union[float, Callable[[jax.Array], jax.Array]]


class SGDState(NamedTuple):
    count: jax.Array          # int32 step counter
    momentum_buffer: Any      # pytree like params (None when unused)


def _lr_at(lr: ScalarOrSchedule, count):
    return lr(count) if callable(lr) else lr


def _advance(state, new_buf):
    return SGDState(count=state.count + 1, momentum_buffer=new_buf)


class ElementwiseRule(NamedTuple):
    """What ``dgc_sgd`` and ``sgd`` offer (``.rule`` on the transformation
    they return) to a caller that can run the update where the gradient
    is made: their ``per_param``, which is elementwise over (g, p, buf),
    with what it closes over in ``update``. The flat DGC step hands it to
    the engine's apply pass (``kernels.payload_update_bits``), which then
    writes p' and buf' of the compressed block instead of a [T] gradient
    that is zero nearly everywhere; the same ``per_param`` is traced
    there, so the mathematics has one copy, here.

    Offered only with a weight-decay mask that can be built for any
    coordinates from static geometry: none, or ``flat.LayoutMask`` in its
    ``runs`` form."""
    per_param: Callable
    lr: ScalarOrSchedule
    weight_decay_mask: Any
    use_buf: bool

    def blocks(self, state, params):
        """The flat buffers :meth:`step` reads and writes, in its order."""
        return (params, state.momentum_buffer) if self.use_buf else (params,)

    def scalars(self, state):
        """``(lr_t, first)`` of the step ``update`` would make from this
        state, as an f32 and an int32 scalar."""
        return (jnp.asarray(_lr_at(self.lr, state.count), jnp.float32),
                (state.count == 0).astype(jnp.int32))

    def advance(self, state, blocks):
        """The state ``update`` would return, from :meth:`step`'s blocks."""
        return _advance(state, blocks[1] if self.use_buf else None)

    def step(self, g, idx, scalars, p, buf=None):
        """``(p', buf')`` (``(p',)`` without a buffer) at the flat
        coordinates ``idx``: ``update``'s arithmetic and the add."""
        lr_t, first = scalars
        # a vector predicate: the rule is also traced into a kernel
        first = jnp.full(p.shape, first) != 0
        m_wd = True
        if self.weight_decay_mask is not None:
            m_wd = jnp.where(self.weight_decay_mask.at(idx),
                             jnp.ones((), p.dtype), jnp.zeros((), p.dtype))
        upd, new_buf = self.per_param(g, p, buf, m_wd, lr_t, first)
        return (p + upd, new_buf) if self.use_buf else (p + upd,)


class _RuledTransformation(optax.GradientTransformation):
    """A ``GradientTransformation`` that also carries ``rule``."""

    def __new__(cls, init, update, rule):
        self = super().__new__(cls, init, update)
        self.rule = rule
        return self


def _wd_mask_flat(weight_decay_mask, params, treedef):
    if weight_decay_mask is None:
        return [True] * treedef.num_leaves
    mask = (weight_decay_mask(params) if callable(weight_decay_mask)
            else weight_decay_mask)
    return jax.tree.leaves(mask)


def _make_sgd(per_param_fn, lr, weight_decay_mask, use_buf):
    """Shared scaffolding: flatten, apply per_param_fn per leaf, unflatten."""

    def init(params):
        buf = jax.tree.map(jnp.zeros_like, params) if use_buf else None
        return SGDState(count=jnp.zeros((), jnp.int32), momentum_buffer=buf)

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("this transformation requires params")
        lr_t = _lr_at(lr, state.count)
        first = state.count == 0
        flat_g, treedef = jax.tree.flatten(grads)
        flat_p = treedef.flatten_up_to(params)
        flat_buf = (treedef.flatten_up_to(state.momentum_buffer)
                    if use_buf else [None] * len(flat_g))
        flat_mask = _wd_mask_flat(weight_decay_mask, params, treedef)

        flat_updates, flat_new_buf = [], []
        for g, p, buf, m_wd in zip(flat_g, flat_p, flat_buf, flat_mask):
            upd, new_buf = per_param_fn(g, p, buf, m_wd, lr_t, first)
            flat_updates.append(upd)
            flat_new_buf.append(new_buf)

        updates = jax.tree.unflatten(treedef, flat_updates)
        new_buf = (jax.tree.unflatten(treedef, flat_new_buf)
                   if use_buf else None)
        return updates, _advance(state, new_buf)

    mask = weight_decay_mask
    if mask is None or getattr(mask, "form", None) == "runs":
        return _RuledTransformation(init, update, ElementwiseRule(
            per_param_fn, lr, mask, use_buf))
    return optax.GradientTransformation(init, update)


def dgc_sgd(lr: ScalarOrSchedule, momentum: float = 0.9,
            dampening: float = 0.0, weight_decay: float = 0.0,
            nesterov: bool = False,
            weight_decay_mask=None) -> optax.GradientTransformation:
    """DGC-split SGD (reference sgd.py:30-70).

    Per parameter: ``d_p = wd·p``; momentum buffer ``buf = m·buf +
    (1-dampening)·d_p`` (first step: ``buf = d_p`` exactly, matching torch's
    clone-init); ``d_p += m·buf`` (nesterov) or ``d_p = buf``; then
    ``p ← p - lr·(d_p + grad)`` — the gradient bypasses the momentum buffer.
    """
    if nesterov and (momentum <= 0 or dampening != 0):
        raise ValueError("Nesterov momentum requires a momentum and zero dampening")

    use_buf = weight_decay != 0 and momentum != 0

    def per_param(g, p, buf, m_wd, lr_t, first):
        if not isinstance(m_wd, (bool, int)):
            # per-coordinate 0/1 mask (flat-buffer path)
            mv = jnp.asarray(m_wd, p.dtype)
            d_p = weight_decay * mv * p
            if momentum != 0 and weight_decay != 0:
                new_buf = jnp.where(first, d_p,
                                    momentum * buf + (1 - dampening) * d_p)
                # a wd=0 coordinate never touches its buffer (sgd.py:51)
                new_buf = mv * new_buf + (1 - mv) * buf
                d_p = d_p + momentum * new_buf if nesterov else new_buf
            else:
                new_buf = buf
            return -lr_t * (mv * d_p + g), new_buf
        wd = weight_decay if m_wd else 0.0  # dgclint: ok[tracer-branch] — a Python bool on this branch (the isinstance test above)
        if wd != 0:  # dgclint: ok[tracer-branch] — a Python float
            d_p = wd * p
            if momentum != 0:
                new_buf = jnp.where(first, d_p,
                                    momentum * buf + (1 - dampening) * d_p)
                d_p = d_p + momentum * new_buf if nesterov else new_buf
            else:
                new_buf = buf
            d_p = d_p + g
        else:
            # buffer still advances on wd-masked params? No: reference keeps
            # per-group wd; a wd=0 group never touches its buffer (sgd.py:51).
            d_p = g
            new_buf = buf
        return -lr_t * d_p, new_buf

    return _make_sgd(per_param, lr, weight_decay_mask, use_buf)


def sgd(lr: ScalarOrSchedule, momentum: float = 0.0, dampening: float = 0.0,
        weight_decay: float = 0.0, nesterov: bool = False,
        weight_decay_mask=None) -> optax.GradientTransformation:
    """Stock torch-semantics SGD for the dense baseline: ``d_p = g + wd·p``;
    ``buf = m·buf + (1-dampening)·d_p`` (first step ``buf = d_p``); nesterov
    ``d_p += m·buf`` else ``d_p = buf``; ``p ← p - lr·d_p``."""
    if nesterov and (momentum <= 0 or dampening != 0):
        raise ValueError("Nesterov momentum requires a momentum and zero dampening")

    use_buf = momentum != 0

    def per_param(g, p, buf, m_wd, lr_t, first):
        if not isinstance(m_wd, (bool, int)):
            # per-coordinate 0/1 mask gates only the wd term; momentum
            # applies to every coordinate (stock torch SGD group semantics)
            d_p = g + weight_decay * jnp.asarray(m_wd, p.dtype) * p
        else:
            d_p = g + (weight_decay * p  # dgclint: ok[tracer-branch] — a Python bool on this branch (the isinstance test above)
                       if (weight_decay != 0 and m_wd) else 0.0)
        if momentum != 0:
            new_buf = jnp.where(first, d_p,
                                momentum * buf + (1 - dampening) * d_p)
            d_p = d_p + momentum * new_buf if nesterov else new_buf
        else:
            new_buf = buf
        return -lr_t * d_p, new_buf

    return _make_sgd(per_param, lr, weight_decay_mask, use_buf)
