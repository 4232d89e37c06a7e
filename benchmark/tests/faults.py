"""Faults planted under the update the window times, one for each number
the exchange check holds that form to (``benchmark/check.py``, PR 44).

Each fault is a function ``fault(arm, patch) -> arm``: ``patch(obj, name,
value)`` sets an attribute and undoes it later (pytest's
``monkeypatch.setattr``; ``Patches`` for a script). On the chip the offered
step runs ``kernels.payload_update_bits`` and, at VGG-16-BN,
``kernels.place_rows``, so a fault is planted round the kernel; off the
chip the engine takes the scatter and the optimizer's own ``update``, and
the same fault is planted round those. Nothing under ``dgc_tpu/`` is
edited. Used by ``test_check.py`` (fixtures, XLA:CPU) and by
``chip_controls.py`` (the cells' own geometry, on the chip).
"""

import jax
import jax.numpy as jnp
import optax


class Patches:
    """``patch(obj, name, value)`` for a script; ``undo()`` puts all back."""

    def __init__(self):
        self._undo = []

    def __call__(self, obj, name, value):
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        for obj, name, value in reversed(self._undo):
            setattr(obj, name, value)
        self._undo.clear()


def _on_the_chip() -> bool:
    from dgc_tpu.ops import kernels
    return kernels.use_pallas()


class _Engine:
    """The engine, but for what ``offered(out, mem_out, mem_in)`` does to
    what an exchange WITH the step's offer returns; the bare exchanges
    pass through."""

    def __init__(self, engine, offered):
        self._engine, self._offered = engine, offered

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def exchange(self, grad, mem, *args, **kwargs):
        out, new = self._engine.exchange(grad, mem, *args, **kwargs)
        if kwargs.get("update") is None:
            return out, new
        return self._offered(out, new, mem)


def _with_engine(arm, offered):
    return arm._replace(setup=arm.setup._replace(
        engine=_Engine(arm.setup.engine, offered)))


def _around_the_pass(patch, before=None, after=None):
    """Wraps ``kernels.payload_update_bits``: ``before`` changes its
    keyword-free arguments (a list), ``after`` its result."""
    from dgc_tpu.ops import kernels
    real = kernels.payload_update_bits

    def payload_update_bits(*args, **kwargs):
        args = list(args)
        if before is not None:
            before(args)
        result = real(*args, **kwargs)
        return result if after is None else after(result)

    patch(kernels, "payload_update_bits", payload_update_bits)


def _without_the_smallest(values, real):
    """``values`` with the smallest nonzero of them where ``real`` zeroed."""
    size = jnp.where(real & (values != 0), jnp.abs(values), jnp.inf)
    return values.at[jnp.argmin(size)].set(0.0)


def drops_a_pair(arm, patch):
    """(a) the pass is given the pairs less the smallest one: its value
    reaches no parameter (the record still says it was sent)."""
    T = arm.setup.engine.T
    if _on_the_chip():
        # payload_update_bits(values, indices, flags, total, state, ...)
        def before(args):
            args[0] = _without_the_smallest(args[0], args[1] < T)
        _around_the_pass(patch, before=before)
        return arm

    def offered(out, new, mem):
        return out.at[:T].set(_without_the_smallest(out[:T], True)), new
    return _with_engine(arm, offered)


def _with_optimizer(arm, patch, update):
    real = arm.dist.optimizer
    patch(arm.dist, "optimizer", optax.GradientTransformation(
        real.init, lambda g, s, p=None: update(real.update(g, s, p), s)))
    return arm


def scales_the_rate(arm, patch):
    """(b) the rule's learning rate scaled by 1 + 2**-10."""
    factor = 1.0 + 2.0 ** -10
    if _on_the_chip():
        # ..., state, rule, scalars = (lr, first)
        def before(args):
            lr, first = args[6]
            args[6] = (lr * factor, first)
        _around_the_pass(patch, before=before)
        return arm
    return _with_optimizer(arm, patch, lambda result, state: (
        jax.tree.map(lambda u: u * factor, result[0]), result[1]))


def keeps_the_old_buffer(arm, patch):
    """(b) the momentum buffer written from the old buffer."""
    if _on_the_chip():
        def before(args):
            rule = args[5]
            args[5] = lambda g, idx, scalars, p, buf: (
                rule(g, idx, scalars, p, buf)[0], buf)
        _around_the_pass(patch, before=before)
        return arm
    return _with_optimizer(arm, patch, lambda result, state: (
        result[0], result[1]._replace(
            momentum_buffer=state.momentum_buffer)))


def misplaces_a_tile(arm, patch):
    """(c) one tile (1,024 elements) of the largest tensor's gradient
    written 1,024 elements off: through ``place_rows`` where the layout
    places that tensor, through ``flatten`` elsewhere."""
    from dgc_tpu.ops import kernels
    layout = arm.setup.layout
    name = max(layout.names, key=layout.sizes.get)
    base = layout.offsets[name]

    def off(flat, piece):
        return jax.lax.dynamic_update_slice(flat, piece, (base + 1024,))

    if name in layout.placed_names():
        real = kernels.place_rows

        def place_rows(x, at, total, into=None):
            flat = real(x, at, total, into=into)
            return off(flat, x.reshape(-1)[:1024]) if at == base else flat

        patch(kernels, "place_rows", place_rows)
    else:
        real_flatten = layout.flatten

        def flatten(tree, place=False):
            flat = real_flatten(tree, place=place)
            # the step's pack says ``place``; the state's initial
            # flattening of the parameters does not
            return off(flat, flat[base:base + 1024]) if place else flat

        patch(layout, "flatten", flatten)
    return arm


def makes_no_offer(arm, patch):
    """(d) the check drives ``step_flat`` with no offer, beside a timed
    step that takes one."""
    real = arm.dist.step_flat
    patch(arm.dist, "step_flat",
          lambda *args, **kwargs: real(*args, **{**kwargs,
                                                 "in_place": False}))
    return arm


def _flip(bits):
    return bits.at[0].set(bits[0] ^ (1 << 5))


def flips_a_record_bit(arm, patch):
    """(e) one bit of the transmit record the offered step leaves."""
    if _on_the_chip():
        _around_the_pass(patch, after=lambda result: (result[0],
                                                      _flip(result[1])))
        return arm
    return _with_engine(arm, lambda out, new, mem: (
        out, {**new, "sent_bits": _flip(new["sent_bits"])}))


def loses_a_velocity_chunk(arm, patch):
    """(f) the offered step's velocity of one chunk (4,096 coordinates)
    left as it came in: neither masked nor compensated."""
    def offered(out, new, mem):
        kept = jax.lax.dynamic_update_slice(
            new["velocities_c"], mem["velocities_c"][:4096], (0,))
        return out, {**new, "velocities_c": kept}
    return _with_engine(arm, offered)


#: fault -> the number of ``run.compared`` that has to see it, first
FAULTS = {
    drops_a_pair: "exchange.update_unexplained_coords",
    scales_the_rate: "exchange.update_unexplained_coords",
    keeps_the_old_buffer: "exchange.buffer_unexplained_coords",
    misplaces_a_tile: "pack.misplaced_coords",
    makes_no_offer: "check.uncovered_kernels",
    flips_a_record_bit: "exchange.record_wrong_bits",
    loses_a_velocity_chunk: "exchange.forms_differ_coords",
}
