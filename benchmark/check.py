"""Correctness of the exchange engine and of the update the window times,
after the timed window, on a device the arms have left.

The engine of the dgc arm (``flat_setup.engine``) is driven twice under the
cell's mesh, at the cell's full geometry, on gradients made from the seed.
The first exchange starts from empty memory and leaves every worker with a
momentum, a velocity and a pending transmit record; the second is the one
that is checked, so the deferred masking of the first step's record is part
of what is checked. What the second exchange must satisfy, against
``benchmark/reference.py``:

* conservation — residual velocity + what reached the parameters equals
  the reference's compensated velocity. Per worker every residual
  coordinate is bitwise either 0 or the reference's float32 value, so a
  state kept in bfloat16 fails; summed over the workers the identity is
  bitwise on one chip and holds to float32 summation order on several;
* fill — every row transmits no more than its quota, and all rows together
  at least ``FILL_FLOOR`` of it;
* selection — recall of the transmitted set against the exact top-k of
  the compensated velocity is at least ``RECALL_FLOOR`` over all buckets,
  and per bucket within sampling error of it.

That second exchange is driven in two forms. Bare, ``engine.exchange``
with the arguments ``update_flat`` gives it: the form that writes a [T]
gradient, which conservation needs. And as the window's step drives it
(PR 44): the checked gradient, cut into the layout's tensors by plain
slices, is packed by ``layout.flatten(tree, place=True)`` as
``training/step.py``'s ``micro`` packs the backward pass's, and handed to
``dist.step_flat(..., in_place=True)`` with the arm's own optimizer, its
state at step ``CHECKED_COUNT``, parameters and a momentum buffer drawn
from the seed, all donated, from the same memory: so the engine gets the
step's offer (``flat.InPlaceUpdate``) and takes it wherever the timed step
does. That form is held to:

* the pack — the packed buffer is bitwise the draw it was cut from;
* the rule — p' and the momentum buffer at every coordinate against
  ``reference.dgc_sgd`` on the gradient that must have reached them: on
  [0, T) the workers' mean of what each sent (the reference's velocity
  where the bare exchange's residual shows the coordinate left it), on
  [T, P) ``reference.momentum_dense`` of the workers' mean gradient;
  within ``UPDATE_ULPS`` float32 spacings, and on several chips the
  rate times what the order of the workers' float32 sum may move that
  gradient by (``sum_tol``: nothing on one chip, and nothing where one
  or two workers sent the coordinate; ``excused_by_sum_order`` says how
  many coordinates rest on it);
* the forms — momentum and velocity (``engine.memory_full``) after the
  offered step are bitwise the bare exchange's;
* the record — the transmit record the offered step left is, bit for
  bit, ``reference.sent_words`` of the coordinates the bare exchange sent;
* the kernels — every Mosaic kernel the timed step lowers is lowered by a
  program of this check (``uncovered``), so a window that times one form
  beside a check that runs another is not correct.

The engine is touched through ``init_memory``, ``exchange``,
``memory_full`` (its canonical, record-free view of the state) and the
memory's ``sent_bits``; the step through ``layout.flatten`` and
``dist.step_flat``. Nothing expected is computed by them.

The check is a handful of small programs (``check_program``), so that it
needs less of the chip than the arm it checks: ``stage_bytes`` is what
``rehearse.py aot`` holds against the chip's memory.
"""

import re
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import reference

#: The engine selects with ``approx_max_k`` at a recall target of 0.95 and
#: with segment-top-2 candidates; on the chip PR 21 measured 0.9665-0.9714
#: (ResNet-50 buckets) and 0.988-0.9966 (VGG 3-D buckets). Below 0.95 the
#: engine sends mass that the algorithm would have kept back. All buckets
#: together are held to the floor itself; one bucket is allowed three
#: standard errors of a 0.95 proportion over its own selections, because
#: a bucket that sends 113 coordinates (VGG's smallest) misses the floor by
#: chance one run in two if it selects at exactly 0.95 (this PR measured
#: 0.956, 0.973 and 0.991 there on three seeds).
RECALL_FLOOR = 0.95


def bucket_recall_floor(sent: float) -> float:
    if sent <= 0:
        return RECALL_FLOOR
    return RECALL_FLOOR - 3.0 * (RECALL_FLOOR * (1 - RECALL_FLOOR)
                                 / sent) ** 0.5
#: Rows that select "threshold passers, capped" may send fewer than their
#: quota; the threshold ladder's lower bound is 0.8 of it
#: (``compress_lower_bound``). The wire has the same shape either way.
FILL_FLOOR = 0.8

#: the optimizer's step count in the offered step: 1 or more, as in every
#: step of the window (at 0 ``dgc_sgd`` clones its buffer from the
#: weight-decay term, a form the window never times)
CHECKED_COUNT = 1
#: the drawn parameters' standard deviation: a trained weight's; the
#: drawn buffer's is what the rule settles at from such parameters,
#: ``weight_decay * PARAM_SCALE / (1 - momentum)``
PARAM_SCALE = 0.02
#: float32 spacings (``reference.ulps_apart``, at the larger of the value
#: before the step and the value expected after it) by which p' and the
#: momentum buffer may lie off the plain rule's before a coordinate is
#: counted. The rule is six float32 operations a coordinate, written in
#: the reference's order and not the program's; what may differ between
#: two sound programs is whether a multiply and the add behind it round
#: once or twice (contraction), and the rule has three such pairs in a
#: row (``m * buf + d_p``, ``d_p + m * buf'``, ``p - lr * x``). Read
#: (PERF.md section 6, PR 44): on the chip, a dozen seeds a one-chip cell,
#: the farthest coordinate of p' AND of the buffer lay 0.0 spacings off:
#: ``payload_update_bits`` and the tail's rule are bitwise the plain
#: rule. On the 2x2, four seeds and three whole runs, the buffer 0.0
#: and p' 0.0 beyond the slack of the workers' sum (``sum_tol`` in
#: ``check_program``), without which 5-10 coordinates a run lay 14-51
#: off, every one in the dense tail (``excused_by_sum_order`` [0, 4-10]):
#: where the mean of four gradients is small beside its terms and p is
#: near 0, the ORDER of a float32 sum shows, and the step's psum and the
#: check's are two collectives. XLA:CPU, which contracts, reads 0.0 / 1.5
#: (p' / buffer) on the fixtures as configured and 5.0 / 1.5 on ``tiny_lm`` at
#: weight decay 0.01 with nesterov (6 coordinates past 2.0: small p,
#: the update all buffer). The controls' farthest coordinate, on the
#: chip: the rate scaled by 1 + 2**-10 29,468-32,368 spacings, a dropped
#: pair 1.06e7-1.14e7, the old buffer kept 3.35e7. So 8: the three
#: contractions and half as much again, three and a half orders under
#: the nearest control
UPDATE_ULPS = 8.0
#: Mosaic kernels of the timed step that this check is not held to lower:
#: identity copies round the MODEL's view of its parameters
#: (``training/step.py`` ``params_view``), part of the forward pass, which
#: no check of the conv nets reaches (PERF.md section 7.1c). Every other
#: kernel the step lowers, this check lowers
MODEL_VIEW_KERNELS = frozenset({"opaque_view", "opaque_view_from"})

#: the counts of the timed form (limit 0), in the order ``run.compared``
#: lists them where several are outside: the one nearest the fault first
NEW_COUNTS = ("misplaced_coords", "record_wrong_bits",
              "buffer_unexplained_coords", "update_unexplained_coords",
              "forms_differ_coords")


def mosaic_kernels(lowered) -> frozenset:
    """The Mosaic kernels a lowered program calls, by the ``name=`` each
    was lowered under (less a ``.N``). None off the chip: there the
    engine takes its ``jax.numpy`` routes."""
    return frozenset(re.sub(r"\.\d+$", "", name) for name in re.findall(
        r'kernel_name = "([^"]+)"', lowered.as_text()))


def _runs(spans):
    """Sorted ``(lo, size)`` spans merged where they adjoin."""
    runs = []
    for lo, size in sorted(spans):
        if runs and runs[-1][1] == lo:
            runs[-1][1] = lo + size
        else:
            runs.append([lo, lo + size])
    return runs


def _in_runs(runs, total: int):
    """[total] bool: True inside ``runs``. Range compares on an iota, one
    pair per run, which fuse into whatever reads the mask: a
    ``searchsorted`` over [total] positions held 24 B a coordinate of
    temporaries (``rehearse.py aot``, PR 27)."""
    pos = jax.lax.iota(jnp.int32, total)
    inside = jnp.zeros((total,), bool)
    for lo, hi in runs:
        inside |= (pos >= lo) & (pos < hi)
    return inside


def _real_mask(layout, total: int):
    """[total] bool: True where the flat layout stores a parameter (row
    tails, the gap and the tail padding are structural zeros, and the
    selection relies on that)."""
    return _in_runs(_runs((layout.offsets[n], layout.sizes[n])
                          for n in layout.names), total)


def _decayed_mask(layout, recipe, total: int):
    """[total] bool: True on the coordinates of the tensors that take
    weight decay (``build_arm``'s ``wd_mask``: all, or all but those
    whose name holds ``recipe["undecayed"]``)."""
    skip = recipe["undecayed"]
    return _in_runs(_runs((layout.offsets[n], layout.sizes[n])
                          for n in layout.names
                          if not (skip and skip in n)), total)


def _bits(x):
    """The bit pattern of ``x`` as float32."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


class Stage(NamedTuple):
    """One program of the check: its jitted function, the (abstract)
    arguments it is called with, and the bytes a chip holds beside it
    while it runs (what an earlier stage left and a later one needs)."""
    name: str
    fn: Callable
    args: Tuple
    held_bytes: int


class Check(NamedTuple):
    run: Callable        # PRNGKey -> counts on the host
    summarize: Callable  # counts, the timed step's kernels -> the result
    stages: Callable     # () -> the programs ``run`` drives, for the law


def exchange_check(arm, seed: int, timed_kernels=()) -> Dict[str, Any]:
    """Run the check; returns its numbers and ``ok``. ``timed_kernels``:
    ``mosaic_kernels`` of the step the window timed."""
    check = check_program(arm)
    if check is None:
        return {"ok": True, "skipped": "the dgc arm has no sparse exchange"}
    return check.summarize(check.run(jax.random.PRNGKey(seed)),
                           timed_kernels)


def program_bytes(compiled) -> int:
    """What a compiled program needs of a chip while it runs, by its
    ``memory_analysis()``: arguments + temporaries + outputs, less the
    outputs that alias (donated) arguments."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def stage_bytes(check: Check) -> Dict[str, int]:
    """Per stage, what the check needs of a chip while that stage runs:
    its compiled program and what is held beside it."""
    return {stage.name: program_bytes(stage.fn.lower(*stage.args).compile())
            + stage.held_bytes for stage in check.stages()}


class _Program:
    """A jitted stage that notes, at its first call, the Mosaic kernels
    it lowers (the lowering is the call's own: JAX keeps it)."""

    def __init__(self, fn):
        self.fn, self.kernels = fn, None

    def __call__(self, *args):
        if self.kernels is None:
            self.kernels = mosaic_kernels(self.fn.lower(*args))
        return self.fn(*args)


def check_program(arm) -> Optional[Check]:
    """The check as a dozen small programs, or None where the arm's
    engine sends nothing sparse. One program held g1, g2, two engine
    memories, two canonical views and the reference's velocity at [T]
    all at once (37.2 B/T at VGG, PR 26); here every stage holds what it
    reads, the engine memory is donated from exchange to exchange, and
    what crosses the offered step is the reference's velocity, the set
    the bare exchange sent and the tail's gradient (5 B/T); whatever
    else a later stage needs is made again from the same keys."""
    from dgc_tpu.utils.compat import shard_map

    engine, layout, dist = arm.setup.engine, arm.setup.layout, arm.dist
    buckets = list(getattr(engine, "buckets", []))
    if not buckets or not engine.payload_size:
        return None
    mem_cfg, recipe = dist.compressor.memory, arm.recipe
    T, total, world = engine.T, layout.total, arm.world
    mesh, axes = arm.mesh, dist.data_axes
    quota = np.concatenate([np.asarray(b.num_selects, np.int64)
                            for b in buckets])
    row_bucket = np.concatenate([np.full(b.rows, i, np.int32)
                                 for i, b in enumerate(buckets)])
    # dgc_sgd keeps a buffer only where it has something to run through it
    buffered = bool(recipe["weight_decay"] and recipe["momentum"])
    # a worker's arrays travel between the stages stacked on a leading
    # axis that is sharded over the workers
    rep, per_worker = P(), P(axes)
    stack = lambda tree: jax.tree.map(lambda x: x[None], tree)
    mine = lambda tree: jax.tree.map(lambda x: x[0], tree)

    def stage(worker, in_specs, out_specs, donate=()):
        return _Program(jax.jit(
            shard_map(worker, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
            donate_argnums=donate))

    def worker_keys(key):
        """[4, 2]: the keys of g1, g2 and of the two exchanges."""
        widx = jax.lax.axis_index(axes[0])
        return jax.random.split(jax.random.fold_in(key, widx), 4)

    def draw(key, i):
        g = jax.random.normal(worker_keys(key)[i], (total,))
        return jnp.where(_real_mask(layout, total), g, 0.0)

    def gradient(key, i):
        """Gradient ``i`` (0, 1) of this worker: a program of its own, so
        that what making it takes is gone when it is read."""
        return draw(key, i)

    def exchange(key, i, grad, mem):
        out, mem = engine.exchange(grad, mine(mem), worker_keys(key)[2 + i],
                                   dist.axis_name, dist.num_nodes,
                                   local_axis=dist.local_axis_name,
                                   local_size=dist.local_size)
        return out, stack(mem)

    def expect(grad, mem):
        """The reference's compensated velocity of the exchange of
        ``grad``, from the engine's canonical view of its memory."""
        before = engine.memory_full(mine(mem))
        _, v_ref = reference.momentum_correction(
            before["momentums"][:T], before["velocities"][:T], grad[:T],
            mem_cfg.momentum, mem_cfg.nesterov)
        return v_ref

    # one chip: no sum, no slack. Several: a float32 sum of at most
    # `world` terms in an order of its own on either side, as in
    # ``compare``; relative to the terms' magnitudes, since a mean of
    # terms that cancel is small and its rounding is not
    sum_tol = 0.0 if world == 1 else 4.0 * world * np.finfo(np.float32).eps

    def expect_tail(grad, mem):
        """What the optimizer must get on [T, P), the tensors that are
        exchanged dense: the reference's correction of the workers' mean
        gradient, which accumulates nothing; and how far the order of
        the workers' sum may move it (the correction passes the mean on
        once, under nesterov once more through the momentum)."""
        before = engine.memory_full(mine(mem))
        mean = jax.lax.psum(grad[T:], axes) / world
        slack = (sum_tol * (1 + mem_cfg.momentum)
                 * jax.lax.psum(jnp.abs(grad[T:]), axes) / world)
        return {"gradient": reference.momentum_dense(
            before["momentums"][T:], mean, mem_cfg.momentum,
            mem_cfg.nesterov)[1], "slack": slack}

    def compare(mem, out, v_ref):
        residual = engine.memory_full(mine(mem))["velocities"][:T]
        bits = jax.lax.bitcast_convert_type
        res_b, ref_b = (bits(residual.astype(jnp.float32), jnp.int32),
                        bits(v_ref, jnp.int32))
        sent = res_b != ref_b
        # bitwise: a residual coordinate is the reference's value or a
        # zero (of either sign: the mask is a multiplication)
        inexact = jnp.sum(sent & ((res_b & 0x7FFFFFFF) != 0))
        applied = out[:T].astype(jnp.float32) * world
        lhs = jax.lax.psum(residual.astype(jnp.float32), axes) + applied
        rhs = jax.lax.psum(v_ref, axes)
        scale = jax.lax.psum(jnp.abs(v_ref), axes)
        # one chip: bitwise. Several: each side is a float32 sum of at
        # most `world` terms in an order of its own
        tol = 0.0 if world == 1 else 4.0 * world * np.finfo(np.float32).eps
        return {"sent": sent,
                "inexact": jax.lax.psum(inexact, axes),
                "unconserved": jnp.sum(jnp.abs(lhs - rhs) > tol * scale)}

    def recall(v_ref, sent):
        hits, counts = [], []
        for b in buckets:
            lo, hi = b.base, b.base + b.rows * b.cols
            h, n = reference.topk_hits(
                v_ref[lo:hi].reshape(b.rows, b.cols),
                sent[lo:hi].reshape(b.rows, b.cols))
            hits.append(h)
            counts.append(n)
            # bucket by bucket: the next bucket's bit patterns are not
            # made before this one's counts are in
            v_ref, sent, _ = jax.lax.optimization_barrier((v_ref, sent, h))
        hits, counts = jnp.concatenate(hits), jnp.concatenate(counts)
        over_quota = jnp.sum(counts > jnp.asarray(quota, jnp.int32))
        return {
            "over_quota_rows": jax.lax.psum(over_quota, axes),
            "hits": jax.lax.psum(hits, axes),
            "sent": jax.lax.psum(counts, axes),
            "sent_outside_rows": jax.lax.psum(
                jnp.sum(sent) - jnp.sum(counts), axes),
        }

    # ---- the form the window times ---------------------------------- #

    def tensors(grad):
        """The checked gradient as the backward pass hands it to the
        step: the layout's tensors, each in its own shape, by plain
        slices of the draw."""
        return {n: grad[layout.offsets[n]:layout.offsets[n]
                        + layout.sizes[n]].reshape(layout.shapes[n])
                for n in layout.names}

    def state_at(key):
        """(p,) or (p, buf): the parameters and dgc_sgd's momentum buffer
        on the real coordinates, every worker's the same."""
        keys = jax.random.split(jax.random.fold_in(key, 2 ** 31 - 1), 2)
        real = _real_mask(layout, total)
        scales = [PARAM_SCALE]
        if buffered:
            scales.append(recipe["weight_decay"] * PARAM_SCALE
                          / (1 - recipe["momentum"]))
        return tuple(jnp.where(real, s * jax.random.normal(k, (total,)), 0.0)
                     for s, k in zip(scales, keys))

    def offered(key, count, tree, blocks, mem):
        """The update as ``training/step.py`` runs it: the gradients
        packed by the layout, then ``step_flat`` with the offer."""
        flat = layout.flatten(tree, place=True)
        misplaced = jnp.sum(_bits(flat) != _bits(draw(key, 1)))
        opt_state = dist.optimizer.init(blocks[0])._replace(
            count=count, momentum_buffer=blocks[1] if buffered else None)
        new_p, opt_state, mem = dist.step_flat(
            flat, opt_state, blocks[0], mine(mem), worker_keys(key)[3],
            engine, in_place=True)
        new = (new_p, opt_state.momentum_buffer) if buffered else (new_p,)
        return new, stack(mem), jax.lax.psum(misplaced, axes)

    def updated(key, count, new, v_ref, sent, tail):
        """p' and buf' against the plain rule's, coordinate by
        coordinate."""
        old = state_at(key)
        mine_sent = jnp.where(sent, v_ref, 0.0)
        senders = jax.lax.psum(sent.astype(jnp.int32), axes)
        g = jnp.concatenate([jax.lax.psum(mine_sent, axes) / world,
                             tail["gradient"].astype(jnp.float32)])
        # a sum of one or two terms is the same in any order
        slack = jnp.concatenate([
            jnp.where(senders > 2, sum_tol * jax.lax.psum(
                jnp.abs(mine_sent), axes) / world, 0.0),
            tail["slack"]])
        lr = jnp.asarray(recipe["lr"](count), jnp.float32)
        want = reference.dgc_sgd(
            old[0], old[1] if buffered else None, g,
            _decayed_mask(layout, recipe, total), lr,
            recipe["momentum"], recipe["dampening"],
            recipe["weight_decay"], recipe["nesterov"])
        out = {}
        # the gradient's slack reaches p' through the rate; the buffer
        # holds no gradient
        for name, got, was, expected, loose in zip(
                ("update", "buffer"), new, old, want, (lr * slack, 0.0)):
            far = reference.ulps_apart(
                got, expected, jnp.maximum(jnp.abs(was), jnp.abs(expected)),
                loose)
            # a NaN is outside
            out[name + "_unexplained"] = jnp.sum(~(far <= UPDATE_ULPS))
            out[name + "_most_ulps"] = jnp.max(far)
            if name == "update":
                # the coordinates only the sum's slack explains: among
                # those three workers or more sent, and in the dense tail
                excused = (far <= UPDATE_ULPS) & (reference.ulps_apart(
                    got, expected, jnp.maximum(jnp.abs(was),
                                               jnp.abs(expected)))
                    > UPDATE_ULPS)
                out["excused"] = jnp.stack([jnp.sum(excused[:T]),
                                            jnp.sum(excused[T:])])
        return out

    def forms(bare, mem, sent):
        """The offered step's memory against the bare exchange's, and its
        transmit record against the coordinates the bare exchange sent."""
        a, b = engine.memory_full(mine(bare)), engine.memory_full(mine(mem))
        # block by block: a slice of the view's concatenation is the
        # block itself, and nothing [P]-sized is written
        differ = sum(jnp.sum(_bits(a[k][part]) != _bits(b[k][part]))
                     for k in sorted(a)
                     for part in (slice(0, T), slice(T, None)))
        wrong = jax.lax.population_count(
            reference.sent_words(sent) ^ mine(mem)["sent_bits"])
        return {"forms_differ": jax.lax.psum(differ, axes),
                "record_wrong_bits": jax.lax.psum(jnp.sum(wrong), axes)}

    blocks_spec = (rep,) * (1 + buffered)
    counts_spec = {k + s: rep for k in ("update", "buffer")[:1 + buffered]
                   for s in ("_unexplained", "_most_ulps")}
    counts_spec["excused"] = rep
    init = stage(lambda: stack(engine.init_memory()), (), per_worker)
    gradient = stage(gradient, (rep, rep), per_worker)
    exchange = stage(exchange, (rep, rep, per_worker, per_worker),
                     (rep, per_worker), donate=(2, 3))
    expect = stage(expect, (per_worker, per_worker), per_worker)
    expect_tail = stage(expect_tail, (per_worker, per_worker),
                        {"gradient": rep, "slack": rep})
    compare = stage(compare, (per_worker, rep, per_worker),
                    {"sent": per_worker, "inexact": rep, "unconserved": rep})
    recall = stage(recall, (per_worker, per_worker), rep)
    cut = stage(tensors, (per_worker,), per_worker)
    drawn_state = stage(state_at, (rep,), blocks_spec)
    offered = stage(offered, (rep, rep, per_worker, blocks_spec, per_worker),
                    (blocks_spec, per_worker, rep), donate=(3, 4))
    updated = stage(updated, (rep, rep, blocks_spec, per_worker, per_worker,
                              rep), counts_spec)
    forms = stage(forms, (per_worker, per_worker, per_worker),
                  {"forms_differ": rep, "record_wrong_bits": rep})
    programs = {"init": init, "gradient": gradient, "exchange": exchange,
                "expect": expect, "expect_tail": expect_tail,
                "compare": compare, "recall": recall, "cut": cut,
                "drawn_state": drawn_state, "offered": offered,
                "updated": updated, "forms": forms}

    def first_memory(key):
        """What the first exchange leaves: the same from the same key."""
        return exchange(key, 0, gradient(key, 0), init())[1]

    def run(key):
        seconds, mark = {}, time.perf_counter()

        def lap(name, tree):
            nonlocal mark
            got = jax.device_get(tree)
            seconds[name] = time.perf_counter() - mark
            mark = time.perf_counter()
            return got

        count = jnp.asarray(CHECKED_COUNT, jnp.int32)
        mem = first_memory(key)
        grad = gradient(key, 1)
        v_ref, tail = expect(grad, mem), expect_tail(grad, mem)
        out, mem = exchange(key, 1, grad, mem)
        counts = compare(mem, out, v_ref)
        del mem, out                  # freed before the next stage runs
        sent = counts.pop("sent")
        counts = lap("bare", {**counts, **recall(v_ref, sent)})
        # the same memory and the same gradient, as the step has them
        mem = first_memory(key)
        new, mem, misplaced = offered(key, count, cut(gradient(key, 1)),
                                      drawn_state(key), mem)
        counts.update(lap("offered", {
            "misplaced": misplaced,
            **updated(key, count, new, v_ref, sent, tail)}))
        del new, v_ref, tail
        bare = exchange(key, 1, gradient(key, 1), first_memory(key))[1]
        counts.update(lap("forms", forms(bare, mem, sent)))
        counts["seconds"] = seconds
        return counts

    def stages():
        def abstract(tree, spec):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

        def shapes(program, *args):
            return jax.eval_shape(program.fn, *args)

        key = abstract(jax.eval_shape(lambda: jax.random.PRNGKey(0)), rep)
        index = abstract(jax.eval_shape(lambda: jnp.int32(0)), rep)
        mem = abstract(shapes(init), per_worker)
        grad = abstract(shapes(gradient, key, index), per_worker)
        v_ref = abstract(shapes(expect, grad, mem), per_worker)
        tail = abstract(shapes(expect_tail, grad, mem), rep)
        out = abstract(shapes(exchange, key, index, grad, mem)[0], rep)
        sent = abstract(shapes(compare, mem, out, v_ref)["sent"], per_worker)
        tree = abstract(shapes(cut, grad), per_worker)
        blocks = abstract(shapes(drawn_state, key), rep)
        nbytes = lambda *trees: sum(
            x.size * x.dtype.itemsize
            for x in jax.tree.leaves(trees)) // world
        kept = nbytes(v_ref, sent) + nbytes(tail) * world
        return (Stage("gradient", gradient.fn, (key, index), nbytes(mem)),
                Stage("expect", expect.fn, (grad, mem), 0),
                Stage("expect_tail", expect_tail.fn, (grad, mem),
                      nbytes(v_ref)),
                Stage("exchange", exchange.fn, (key, index, grad, mem),
                      nbytes(v_ref) + nbytes(tail) * world),
                Stage("compare", compare.fn, (mem, out, v_ref), 0),
                Stage("recall", recall.fn, (v_ref, sent), 0),
                # the offered step's arguments are made one after the
                # other, each beside those made before it
                Stage("exchange.again", exchange.fn, (key, index, grad, mem),
                      kept),
                Stage("cut", cut.fn, (grad,), kept + nbytes(mem)),
                Stage("drawn_state", drawn_state.fn, (key,),
                      kept + nbytes(mem) + nbytes(tree)),
                Stage("offered", offered.fn, (key, index, tree, blocks, mem),
                      kept),
                Stage("updated", updated.fn,
                      (key, index, blocks, v_ref, sent, tail), nbytes(mem)),
                Stage("exchange.bare", exchange.fn, (key, index, grad, mem),
                      nbytes(mem) + nbytes(sent)),
                Stage("forms", forms.fn, (mem, mem, sent), 0))

    def summarize(got, timed_kernels=()):
        nb = len(buckets)
        hits_b = np.bincount(row_bucket, weights=got["hits"], minlength=nb)
        sent_b = np.bincount(row_bucket, weights=got["sent"], minlength=nb)
        recall = [float(h / s) if s else 0.0
                  for h, s in zip(hits_b, sent_b)]
        pooled = float(hits_b.sum() / max(sent_b.sum(), 1))
        fill = float(got["sent"].sum() / (quota.sum() * world))
        lowered = frozenset().union(
            *(p.kernels or () for p in programs.values()))
        uncovered = sorted(frozenset(timed_kernels) - MODEL_VIEW_KERNELS
                           - lowered)
        result = {
            "T": int(T), "payload_size": int(engine.payload_size),
            "quota": int(quota.sum()), "buckets": nb, "world": int(world),
            "inexact_residual_coords": int(got["inexact"]),
            "unconserved_coords": int(got["unconserved"]),
            "over_quota_rows": int(got["over_quota_rows"]),
            "sent_outside_rows": int(got["sent_outside_rows"]),
            "fill": fill, "sent_per_bucket": [int(n) for n in sent_b],
            "recall": pooled, "recall_per_bucket": recall,
            "recall_floor": RECALL_FLOOR,
            "recall_floor_per_bucket": [float(bucket_recall_floor(s))
                                        for s in sent_b],
            "fill_floor": FILL_FLOOR,
            # the form the window times
            "checked_count": CHECKED_COUNT,
            "misplaced_coords": int(got["misplaced"]),
            "update_unexplained_coords": int(got["update_unexplained"]),
            "update_most_ulps": float(got["update_most_ulps"]),
            "buffer_unexplained_coords": int(
                got.get("buffer_unexplained", 0)),
            "buffer_most_ulps": float(got.get("buffer_most_ulps", 0.0)),
            "update_ulps": UPDATE_ULPS,
            # coordinates of p' that only the slack of the workers' sum
            # explains: [of those three or more sent, of the dense tail]
            "excused_by_sum_order": [int(n) for n in got["excused"]],
            "forms_differ_coords": int(got["forms_differ"]),
            "record_wrong_bits": int(got["record_wrong_bits"]),
            "uncovered_kernels": uncovered,
            "kernels": {"timed": sorted(timed_kernels),
                        "checked": sorted(lowered),
                        "not_held_to": sorted(MODEL_VIEW_KERNELS
                                              & frozenset(timed_kernels))},
            "parts_s": got["seconds"],
        }
        result["ok"] = bool(
            result["inexact_residual_coords"] == 0
            and result["unconserved_coords"] == 0
            and result["over_quota_rows"] == 0
            and result["sent_outside_rows"] == 0
            and FILL_FLOOR <= fill <= 1.0
            and pooled >= RECALL_FLOOR
            and all(r >= bucket_recall_floor(s)
                    for r, s in zip(recall, sent_b))
            and not any(result[k] for k in NEW_COUNTS)
            and not uncovered)
        return result

    return Check(run, summarize, stages)

