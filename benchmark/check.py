"""Correctness of the exchange engine, after the timed window, on a device
the arms have left.

The engine of the dgc arm (``flat_setup.engine``) is driven twice under the
cell's mesh, at the cell's full geometry, on gradients made from the seed.
The first exchange starts from empty memory and leaves every worker with a
momentum, a velocity and a pending transmit record; the second is the one
that is checked, so the deferred masking of the first step's record is part
of what is checked. The engine is touched through three calls only:
``init_memory``, ``exchange`` (with the arguments ``update_flat`` gives it)
and ``memory_full`` (its canonical, record-free view of the state). What
the second exchange must satisfy, against ``benchmark/reference.py``:

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

The check is a handful of small programs (``check_program``), so that it
needs less of the chip than the arm it checks: ``stage_bytes`` is what
``rehearse.py aot`` holds against the chip's memory.
"""

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


def _real_mask(layout, total: int):
    """[total] bool: True where the flat layout stores a parameter (row
    tails, the gap and the tail padding are structural zeros, and the
    selection relies on that). Range compares on an iota, one pair per run
    of adjoining tensors, which fuse into whatever reads the mask: a
    ``searchsorted`` over [total] positions held 24 B a coordinate of
    temporaries (``rehearse.py aot``, PR 27)."""
    runs = []
    for lo, size in sorted((layout.offsets[n], layout.sizes[n])
                           for n in layout.names):
        if runs and runs[-1][1] == lo:
            runs[-1][1] = lo + size
        else:
            runs.append([lo, lo + size])
    pos = jax.lax.iota(jnp.int32, total)
    real = jnp.zeros((total,), bool)
    for lo, hi in runs:
        real |= (pos >= lo) & (pos < hi)
    return real


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
    summarize: Callable  # counts -> the result with ``ok``
    stages: Callable     # () -> the programs ``run`` drives, for the law


def exchange_check(arm, seed: int) -> Dict[str, Any]:
    """Run the check; returns its numbers and ``ok``."""
    check = check_program(arm)
    if check is None:
        return {"ok": True, "skipped": "the dgc arm has no sparse exchange"}
    return check.summarize(check.run(jax.random.PRNGKey(seed)))


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


def check_program(arm) -> Optional[Check]:
    """The check as five small programs, or None where the arm's engine
    sends nothing sparse. One program held g1, g2, two engine memories,
    two canonical views and the reference's velocity at [T] all at once
    (37.2 B/T at VGG, PR 26); here every stage holds what it reads, the
    engine memory is donated from exchange to exchange, and only the
    reference's velocity (4 B/T) crosses the second exchange."""
    from dgc_tpu.utils.compat import shard_map

    engine, layout, dist = arm.setup.engine, arm.setup.layout, arm.dist
    buckets = list(getattr(engine, "buckets", []))
    if not buckets or not engine.payload_size:
        return None
    mem_cfg = dist.compressor.memory
    T, total, world = engine.T, layout.total, arm.world
    mesh, axes = arm.mesh, dist.data_axes
    quota = np.concatenate([np.asarray(b.num_selects, np.int64)
                            for b in buckets])
    row_bucket = np.concatenate([np.full(b.rows, i, np.int32)
                                 for i, b in enumerate(buckets)])
    # a worker's arrays travel between the stages stacked on a leading
    # axis that is sharded over the workers
    rep, per_worker = P(), P(axes)
    stack = lambda tree: jax.tree.map(lambda x: x[None], tree)
    mine = lambda tree: jax.tree.map(lambda x: x[0], tree)

    def stage(worker, in_specs, out_specs, donate=()):
        return jax.jit(shard_map(worker, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False),
                       donate_argnums=donate)

    def worker_keys(key):
        """[4, 2]: the keys of g1, g2 and of the two exchanges."""
        widx = jax.lax.axis_index(axes[0])
        return jax.random.split(jax.random.fold_in(key, widx), 4)

    def gradient(key, i):
        """Gradient ``i`` (0, 1) of this worker: a program of its own, so
        that what making it takes is gone when it is read."""
        g = jax.random.normal(worker_keys(key)[i], (total,))
        return jnp.where(_real_mask(layout, total), g, 0.0)

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

    init = stage(lambda: stack(engine.init_memory()), (), per_worker)
    gradient = stage(gradient, (rep, rep), per_worker)
    exchange = stage(exchange, (rep, rep, per_worker, per_worker),
                     (rep, per_worker), donate=(2, 3))
    expect = stage(expect, (per_worker, per_worker), per_worker)
    compare = stage(compare, (per_worker, rep, per_worker),
                    {"sent": per_worker, "inexact": rep, "unconserved": rep})
    recall = stage(recall, (per_worker, per_worker), rep)

    def run(key):
        mem = exchange(key, 0, gradient(key, 0), init())[1]
        grad = gradient(key, 1)
        v_ref = expect(grad, mem)
        out, mem = exchange(key, 1, grad, mem)
        counts = compare(mem, out, v_ref)
        del mem, out                  # freed before the last stage runs
        counts.update(recall(v_ref, counts.pop("sent")))
        return jax.device_get(counts)

    def stages():
        def abstract(tree, spec):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

        key = abstract(jax.eval_shape(lambda: jax.random.PRNGKey(0)), rep)
        index = abstract(jax.eval_shape(lambda: jnp.int32(0)), rep)
        mem = abstract(jax.eval_shape(init), per_worker)
        grad = abstract(jax.eval_shape(gradient, key, index), per_worker)
        v_ref = abstract(jax.eval_shape(expect, grad, mem), per_worker)
        out = abstract(jax.eval_shape(exchange, key, index, grad, mem)[0],
                       rep)
        sent = abstract(jax.eval_shape(compare, mem, out, v_ref)["sent"],
                        per_worker)
        nbytes = lambda tree: sum(x.size * x.dtype.itemsize
                                  for x in jax.tree.leaves(tree)) // world
        return (Stage("gradient", gradient, (key, index), nbytes(mem)),
                Stage("expect", expect, (grad, mem), 0),
                Stage("exchange", exchange, (key, index, grad, mem),
                      nbytes(v_ref)),
                Stage("compare", compare, (mem, out, v_ref), 0),
                Stage("recall", recall, (v_ref, sent), 0))

    def summarize(got):
        nb = len(buckets)
        hits_b = np.bincount(row_bucket, weights=got["hits"], minlength=nb)
        sent_b = np.bincount(row_bucket, weights=got["sent"], minlength=nb)
        recall = [float(h / s) if s else 0.0
                  for h, s in zip(hits_b, sent_b)]
        pooled = float(hits_b.sum() / max(sent_b.sum(), 1))
        fill = float(got["sent"].sum() / (quota.sum() * world))
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
        }
        result["ok"] = bool(
            result["inexact_residual_coords"] == 0
            and result["unconserved_coords"] == 0
            and result["over_quota_rows"] == 0
            and result["sent_outside_rows"] == 0
            and FILL_FLOOR <= fill <= 1.0
            and pooled >= RECALL_FLOOR
            and all(r >= bucket_recall_floor(s)
                    for r, s in zip(recall, sent_b)))
        return result

    return Check(run, summarize, stages)
