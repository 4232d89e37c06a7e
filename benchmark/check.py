"""Correctness of the exchange engine, outside the timed window.

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
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

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
    selection relies on that)."""
    order = sorted(layout.names, key=lambda n: layout.offsets[n])
    starts = np.array([layout.offsets[n] for n in order], np.int64)
    ends = starts + np.array([layout.sizes[n] for n in order], np.int64)
    pos = jnp.arange(total, dtype=jnp.int32)
    owner = jnp.searchsorted(jnp.asarray(starts, jnp.int32), pos,
                             side="right") - 1
    return (owner >= 0) & (pos < jnp.asarray(ends, jnp.int32)[owner])


def exchange_check(arm, seed: int) -> Dict[str, Any]:
    """Run the check; returns its numbers and ``ok``."""
    program = check_program(arm)
    if program is None:
        return {"ok": True, "skipped": "the dgc arm has no sparse exchange"}
    run, summarize = program
    return summarize(jax.device_get(run(jax.random.PRNGKey(seed))))


def check_program(arm):
    """(the jitted check, PRNGKey -> counts; counts -> result), or None
    where the arm's engine sends nothing sparse."""
    from dgc_tpu.utils.compat import shard_map

    engine, layout, dist = arm.setup.engine, arm.setup.layout, arm.dist
    buckets = list(getattr(engine, "buckets", []))
    if not buckets or not engine.payload_size:
        return None
    mem_cfg = dist.compressor.memory
    T, total, world = engine.T, layout.total, arm.world
    axes = dist.data_axes
    quota = np.concatenate([np.asarray(b.num_selects, np.int64)
                            for b in buckets])
    row_bucket = np.concatenate([np.full(b.rows, i, np.int32)
                                 for i, b in enumerate(buckets)])

    def exchange(grad, mem, key):
        return engine.exchange(grad, mem, key, dist.axis_name,
                               dist.num_nodes,
                               local_axis=dist.local_axis_name,
                               local_size=dist.local_size)

    def worker(key):
        widx = jax.lax.axis_index(axes[0])
        k1, k2, ks1, ks2 = jax.random.split(
            jax.random.fold_in(key, widx), 4)
        real = _real_mask(layout, total)
        g1 = jnp.where(real, jax.random.normal(k1, (total,)), 0.0)
        g2 = jnp.where(real, jax.random.normal(k2, (total,)), 0.0)
        _, mem1 = exchange(g1, engine.init_memory(), ks1)
        before = engine.memory_full(mem1)
        out, mem2 = exchange(g2, mem1, ks2)
        after = engine.memory_full(mem2)

        _, v_ref = reference.momentum_correction(
            before["momentums"][:T], before["velocities"][:T], g2[:T],
            mem_cfg.momentum, mem_cfg.nesterov)
        residual = after["velocities"][:T]
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
        unconserved = jnp.sum(jnp.abs(lhs - rhs) > tol * scale)

        hits, counts = [], []
        for b in buckets:
            lo, hi = b.base, b.base + b.rows * b.cols
            h, n = reference.topk_hits(
                v_ref[lo:hi].reshape(b.rows, b.cols),
                sent[lo:hi].reshape(b.rows, b.cols))
            hits.append(h)
            counts.append(n)
        hits, counts = jnp.concatenate(hits), jnp.concatenate(counts)
        over_quota = jnp.sum(counts > jnp.asarray(quota, jnp.int32))
        return {
            "inexact": jax.lax.psum(inexact, axes),
            "unconserved": unconserved,
            "over_quota_rows": jax.lax.psum(over_quota, axes),
            "hits": jax.lax.psum(hits, axes),
            "sent": jax.lax.psum(counts, axes),
            "sent_outside_rows": jax.lax.psum(
                jnp.sum(sent) - jnp.sum(counts), axes),
        }

    run = jax.jit(shard_map(worker, mesh=arm.mesh, in_specs=P(),
                            out_specs=P(), check_vma=False))

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
            "fill": fill,
            "recall": pooled, "recall_per_bucket": recall,
            "recall_floor": RECALL_FLOOR,
            "recall_floor_per_bucket": [bucket_recall_floor(s)
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

    return run, summarize
