"""Correctness of the timed STEP, against the configuration's plain
reference of its model.

What is compared is what the window then drives: set-up builds each arm's
compiled step and state once, drives them from the seed through three
dispatches (``run.py``: the first and its ``SOLO_WARMUP_STEPS``) of the
window's own call (``ArmRun.dispatch``) on the cell's own batch under the
cell's mesh, and hands the same objects to
the window. A ``Follower`` copies the arm's state to the host before the
first dispatch and after each one; nothing else of the program is read.
After the window has closed, the device peak has been read and the arms'
states are freed, the reference follows:

``dense`` arm — an independent trajectory: the configuration's
``loss_and_grads`` (plain ``jax.numpy``, float32, matmuls at ``highest``)
and a plain SGD update written here (torch semantics: ``d = g + wd*p``,
``buf = m*buf + d``, nesterov ``d + m*buf``), from the seed's weights
through every followed step. Compared: every step's loss; the first
gradient as the optimizer got it, worked out from the state after one
step (its momentum buffer, or the parameters' change where there is no
momentum); the norm of the parameters' change after all followed steps.

``dgc`` arm — which coordinates DGC sends is the engine's (approximate)
choice, so no independent trajectory exists. Each followed step is held
to DGC's conservation instead, anchored at the program's own state before
that step: what reached the parameters (their change over the learning
rate, less the weight-decay term) plus what stayed behind (the workers'
mean residual velocity) must equal the reference's momentum correction
(``benchmark/reference.py``) of the workers' mean memory with the
reference's gradient at those parameters. At the first step that is the
gradient itself. Compared: the loss at the first step of every dispatch,
and the conserved velocity of every step.

A loop of kind ``scan`` runs ``k`` steps in a dispatch and leaves no
state after one step: there the losses and the dense arm's parameter
change are compared, and no gradient.

Tensor by tensor, an error is the norm of the difference over the larger
of the reference's norm of that tensor and of the median tensor (some
gradients are all but zero); the gap between the two norms can be no
larger. The reference module states the four limits with the readings
each was set from: ``LOSS_RTOL``, ``GRAD_RTOL``, ``UPDATE_RTOL``,
``CONSERVED_RTOL``.
"""

import statistics
from typing import Any, Dict

import jax
import numpy as np

from benchmark import cells, reference as exchange_reference
from dgc_tpu.utils.pytree import named_flatten, named_unflatten


class Follower:
    """Host copies of one arm's state round its first dispatches."""

    def __init__(self, cell, arm):
        ref = cell.config["reference"]
        self.reference = None if ref is None else cells.load_reference(ref)
        self.arm = arm
        self.snaps = []

    def snapshot(self, run):
        """Copy ``run``'s state (and its last dispatch's losses) to the
        host; a configuration without a reference copies nothing."""
        if self.reference is None:
            return
        s = run.state
        self.snaps.append(jax.device_get({
            "params": s.params, "opt": s.opt_state, "memory": s.memory,
            "losses": run.losses[-1] if run.losses else None}))


def compare(cell, followers: Dict[str, Follower], batch) -> Dict[str, Any]:
    """The verdict and its numbers. ``batch`` is what every followed
    dispatch was fed; the arms' device state is freed by now."""
    cfg = cell.config
    if cfg["reference"] is None:
        return {"ok": True, "skipped": cfg["reference_why"]}
    batch = jax.device_get(batch)
    scan = cell.traffic["loop"] == "scan"

    def batch_at(i):
        """What step ``i`` of a dispatch reads (build._make_k_loop)."""
        if not scan:
            return batch
        return tuple(b[i % b.shape[0]] for b in batch)

    with jax.default_matmul_precision("highest"):
        arms = {name: (_follow_dgc if name == "dgc" else _follow_dense)(
            f, batch_at) for name, f in followers.items()}
    ref = next(iter(followers.values())).reference
    limits = {"loss_rel_err": ref.LOSS_RTOL, "grad_rel_err": ref.GRAD_RTOL,
              "update_norm_gap": ref.UPDATE_RTOL,
              "conserved_rel_err": ref.CONSERVED_RTOL}
    ok = all(np.isfinite(arm[key]["max"]) and arm[key]["max"] <= limit
             for arm in arms.values() for key, limit in limits.items()
             if key in arm)
    return {"reference": cfg["reference"], "arms": arms, "limits": limits,
            "ok": bool(ok)}


def _loss_and_grads(reference):
    """The reference's ``loss_and_grads``, jitted; where the module states
    ``ROW_BLOCK``, called on that many rows at a time, the blocks' losses
    and gradients summed weighted by their share of the rows (rows are of
    one length, so of the tokens): a plain float32 reference keeps every
    layer's [heads, S, S] scores for its backward pass, and a whole batch
    of long rows does not fit beside them."""
    whole = jax.jit(reference.loss_and_grads)
    block = getattr(reference, "ROW_BLOCK", None)
    if block is None:
        return whole

    def blocked(params, inputs, labels):
        rows = inputs.shape[0]
        per_row = labels.shape[0] // rows     # token-major labels: [B*S]
        loss, grads = 0.0, None
        for lo in range(0, rows, block):
            hi = min(lo + block, rows)
            share = (hi - lo) / rows
            l, g = whole(params, inputs[lo:hi],
                         labels[lo * per_row:hi * per_row])
            loss = loss + share * l
            grads = jax.tree.map(
                lambda g_: share * g_, g) if grads is None else jax.tree.map(
                lambda acc, g_: acc + share * g_, grads, g)
        return loss, grads

    return blocked


# ---------------------------------------------------------------------- #
# the two arms                                                           #
# ---------------------------------------------------------------------- #

def _by_tensor(tree) -> Dict[str, np.ndarray]:
    return named_flatten(jax.device_get(tree))[0]


def _follow_dense(f: Follower, batch_at):
    lay, recipe, snaps = f.arm.setup.layout, f.arm.recipe, f.snaps
    k = int(np.size(snaps[1]["losses"]))
    steps = k * (len(snaps) - 1)
    named = lambda flat: _by_tensor(lay.unflatten(np.asarray(flat)))
    p0 = named(snaps[0]["params"])
    decayed = _decayed(recipe, p0)
    wd, m = recipe["weight_decay"], recipe["momentum"]
    grad = _loss_and_grads(f.reference)

    # the reference's own trajectory, float32 on the default device
    params = lay.unflatten(np.asarray(snaps[0]["params"]))
    buf, ref_losses, g0 = None, [], None
    for t in range(steps):
        loss, g = grad(params, *batch_at(t % k))
        ref_losses.append(float(loss))
        if t == 0:
            g0 = _by_tensor(g)
        d = _named_map(lambda n, g_, p: g_ + wd * p if decayed[n] else g_,
                       g, params)
        if m:
            buf = d if buf is None else jax.tree.map(
                lambda b, d_: m * b + (1 - recipe["dampening"]) * d_, buf, d)
            d = (jax.tree.map(lambda d_, b: d_ + m * b, d, buf)
                 if recipe["nesterov"] else buf)
        lr = float(recipe["lr"](t))
        params = jax.tree.map(lambda p, d_: p - lr * d_, params, d)
    p_ref = _by_tensor(params)

    losses = np.concatenate([np.ravel(s["losses"]) for s in snaps[1:]])
    out = {"steps": steps, "loss_rel_err": _losses(losses, ref_losses)}
    if k == 1:
        p1 = named(snaps[1]["params"])
        if m:
            d1 = named(snaps[1]["opt"].momentum_buffer)
        else:
            lr0 = float(recipe["lr"](0))
            d1 = {n: (p0[n] - p1[n]) / lr0 for n in p0}
        out["grad_rel_err"] = _worst(
            {n: d1[n] - (wd * p0[n] if decayed[n] else 0.0) for n in p0}, g0)
    p_end = named(snaps[-1]["params"])
    gaps = _leafwise({n: p_end[n] - p0[n] for n in p0},
                     {n: p_ref[n] - p0[n] for n in p0})["norm_gap"]
    out["update_norm_gap"] = _summary(gaps)
    return out


def _follow_dgc(f: Follower, batch_at):
    arm, snaps = f.arm, f.snaps
    lay, recipe, engine = arm.setup.layout, arm.recipe, arm.setup.engine
    mem_cfg = arm.dist.compressor.memory
    if getattr(mem_cfg, "gradient_clipping", None) is not None:
        raise cells.CellError(
            "model check: the dgc arm clips its gradients, which the plain "
            "reference does not; a configuration with a reference states "
            "no clipping")
    k = int(np.size(snaps[1]["losses"]))
    named = lambda flat: _by_tensor(lay.unflatten(np.asarray(flat)))
    grad = _loss_and_grads(f.reference)
    wd, m_opt = recipe["weight_decay"], recipe["momentum"]
    decayed = _decayed(recipe, named(snaps[0]["params"]))

    def mean_memory(snap):
        """The workers' mean canonical (momentum, velocity), by tensor."""
        mem, world = snap["memory"], arm.world
        total = {"momentums": 0.0, "velocities": 0.0}
        for w in range(world):
            full = jax.device_get(engine.memory_full(
                {key: v[w] for key, v in mem.items()}))
            for key in total:
                total[key] = total[key] + np.asarray(full[key], np.float64)
        return {key: named(v / world) for key, v in total.items()}

    losses, ref_losses, conserved, buf = [], [], [], None
    memories = [mean_memory(s) for s in snaps] if k == 1 else None
    for d in range(len(snaps) - 1):
        before, after = snaps[d], snaps[d + 1]
        loss, g = grad(lay.unflatten(np.asarray(before["params"])),
                       *batch_at(0))
        losses.append(float(np.ravel(after["losses"])[0]))
        ref_losses.append(float(loss))
        if k != 1:
            continue
        g = _by_tensor(g)
        p, p_next = named(before["params"]), named(after["params"])
        mem, mem_next = memories[d], memories[d + 1]
        lr = float(recipe["lr"](d))
        prog, want = {}, {}
        # dgc_sgd: momentum runs over the weight-decay term alone
        term = {n: wd * p[n] if decayed[n] else 0.0 * p[n] for n in p}
        if wd and m_opt:
            buf = term if buf is None else {
                n: m_opt * buf[n] + (1 - recipe["dampening"]) * term[n]
                for n in p}
            term = {n: (term[n] + m_opt * buf[n] if recipe["nesterov"]
                        else buf[n]) if decayed[n] else term[n] for n in p}
        for n in p:
            applied = (p[n].astype(np.float64) - p_next[n]) / lr - term[n]
            prog[n] = applied + mem_next["velocities"][n]
            _, want[n] = exchange_reference.momentum_correction(
                mem["momentums"][n], mem["velocities"][n], g[n],
                mem_cfg.momentum, mem_cfg.nesterov)
        conserved.append(_worst(prog, want))
    out = {"steps": k * (len(snaps) - 1),
           "loss_rel_err": _losses(losses, ref_losses)}
    if conserved:
        out["conserved_rel_err"] = max(
            conserved, key=lambda c: _nan_first(c["max"]))
    return out


# ---------------------------------------------------------------------- #
# arithmetic                                                             #
# ---------------------------------------------------------------------- #

def _decayed(recipe, named_params) -> Dict[str, bool]:
    """Which tensors take weight decay (build_arm's ``wd_mask``)."""
    skip = recipe["undecayed"]
    return {n: bool(recipe["weight_decay"]) and not (skip and skip in n)
            for n in named_params}


def _named_map(fn, *trees):
    """``jax.tree.map`` whose function also gets the tensor's name."""
    named = [named_flatten(t)[0] for t in trees]
    treedef = named_flatten(trees[0])[1]
    return named_unflatten(
        {n: fn(n, *(t[n] for t in named)) for n in named[0]}, treedef)


def _nan_first(x: float) -> float:
    """Sort key under which a NaN is the largest."""
    return float("inf") if np.isnan(x) else x


def _summary(by_name: Dict[str, float]) -> Dict[str, Any]:
    worst = max(by_name, key=lambda n: _nan_first(by_name[n]))
    return {"max": by_name[worst], "worst_tensor": worst,
            "by_tensor": by_name}


def _leafwise(prog, ref) -> Dict[str, Dict[str, float]]:
    """Per tensor: the norm of the difference, and the gap between the
    norms, over max(the reference's norm, its median tensor's norm)."""
    if set(prog) != set(ref):
        raise cells.CellError(
            f"the reference returns gradients for {sorted(ref)}, the model "
            f"has {sorted(prog)}")
    norms = {n: float(np.linalg.norm(np.asarray(ref[n], np.float64)))
             for n in ref}
    floor = statistics.median(norms.values())
    err, gap = {}, {}
    for n, want in ref.items():
        got = np.asarray(prog[n], np.float64)
        diff = float(np.linalg.norm(got - np.asarray(want, np.float64)))
        scale = max(norms[n], floor)
        if scale == 0.0:                      # every reference tensor is 0
            scale = 1.0
        err[n] = diff / scale
        gap[n] = abs(float(np.linalg.norm(got)) - norms[n]) / scale
    return {"rel_err": err, "norm_gap": gap}


def _worst(prog, ref) -> Dict[str, Any]:
    both = _leafwise(prog, ref)
    out = _summary(both["rel_err"])
    out["norm_gap_max"] = max(both["norm_gap"].values(), key=_nan_first)
    return out


def _losses(got, want) -> Dict[str, Any]:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want) / np.abs(want)
    return {"max": float(max(err, key=_nan_first)),
            "program": got.tolist(), "reference": want.tolist()}
