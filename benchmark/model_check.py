"""Correctness of the timed STEP, against the configuration's plain
reference of its model.

What is compared is what the window then drives: set-up builds each arm's
compiled step and state once, drives them from the seed through three
dispatches (``run.py``: the first and its ``SOLO_WARMUP_STEPS``) of the
window's own call (``ArmRun.dispatch``) on the cell's own batch under the
cell's mesh, and hands the same objects to
the window. A ``Follower`` copies what the follow will read of the arm's
state (and no more) before the first dispatch and after each one, into
files under the process's temporary directory, so that the host holds
none of it through the window; nothing else of the program is read.
After the window has closed, the device peak has been read and the arms'
states are freed, the reference follows, and reads the files back a piece
of a tensor at a time:

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

import math
import os
import shutil
import statistics
import tempfile
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

import jax
import numpy as np

from benchmark import cells, reference as exchange_reference
from dgc_tpu.utils.pytree import named_flatten, named_unflatten


class _File(NamedTuple):
    """An array a ``Follower`` wrote, read back whole, a row or a run of
    its elements at a time; never mapped, since a map's pages stay in the
    process's resident set for as long as the map lives."""
    path: str
    dtype: Any
    shape: Tuple[int, ...]

    def read(self, lo: int = 0, hi: Optional[int] = None) -> np.ndarray:
        """Elements [lo, hi) of the flattened array."""
        hi = int(np.prod(self.shape)) if hi is None else hi
        return np.fromfile(self.path, self.dtype, hi - lo,
                           offset=lo * self.dtype.itemsize)

    def whole(self) -> np.ndarray:
        return self.read().reshape(self.shape)

    def row(self, w: int) -> np.ndarray:
        """``array[w]``: one worker's share of a per-worker array."""
        n = int(np.prod(self.shape[1:]))
        return self.read(w * n, (w + 1) * n).reshape(self.shape[1:])


class Follower:
    """What a follow reads of one arm's state round its first dispatches,
    in files of its own directory under the temporary one until
    ``compare`` reads them back. ``snapshots``: how many there will be,
    one before the first dispatch and one after each."""

    def __init__(self, cell, arm, snapshots: int):
        ref = cell.config["reference"]
        self.reference = None if ref is None else cells.load_reference(ref)
        self.arm, self.snapshots = arm, snapshots
        # steps in a dispatch: a loop of kind ``scan`` leaves no state
        # after one step, so no gradient is read there
        self.k = cell.traffic["k"] if cell.traffic["loop"] == "scan" else 1
        self.snaps = []
        self._dir = None

    def reads(self, index: int) -> Tuple[str, ...]:
        """The parts of the state the follow reads of snapshot ``index``.
        ``_follow_dgc``: the parameters and the engine's memory round
        every step (the parameters before each dispatch where ``k`` steps
        run in one). ``_follow_dense``: the parameters before the first
        dispatch and after the last, and after the first the momentum
        buffer, which holds the first gradient (without momentum the
        parameters do)."""
        last = self.snapshots - 1
        if self.arm.name == "dgc":
            if self.k == 1:
                return ("params", "memory")
            return ("params",) if index < last else ()
        parts = ["params"] if index in (0, last) else []
        if self.k == 1 and index == 1:
            parts.append("momentum" if self.arm.recipe["momentum"]
                         else "params")
        return tuple(dict.fromkeys(parts))

    @staticmethod
    def _arrays(state, part: str) -> Dict[str, Any]:
        """The arrays of ``part`` of ``state``, by name."""
        if part == "momentum":
            return {"momentum": state.opt_state.momentum_buffer}
        if part == "params":
            return {"params": state.params}
        return {"memory." + key: v for key, v in state.memory.items()}

    def kept_bytes(self, state) -> int:
        """Bytes of all this follower's files, from ``state`` or its
        shapes (``rehearse.memory_law``'s host term); 0 without a
        reference."""
        if self.reference is None:
            return 0
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for i in range(self.snapshots) for part in self.reads(i)
                   for a in self._arrays(state, part).values())

    def snapshot(self, run):
        """Write what the follow reads of ``run``'s state to files, an
        array at a time, and keep its last dispatch's losses; a
        configuration without a reference copies nothing."""
        if self.reference is None:
            return
        if self._dir is None:
            self._dir = tempfile.mkdtemp(prefix="dgc_bench_follow_")
        index, snap = len(self.snaps), {}
        for part in self.reads(index):
            for name, array in self._arrays(run.state, part).items():
                host = np.ascontiguousarray(jax.device_get(array))
                snap[name] = _File(os.path.join(self._dir, f"{index}.{name}"),
                                   host.dtype, host.shape)
                host.tofile(snap[name].path)
        snap["losses"] = (jax.device_get(run.losses[-1]) if run.losses
                          else None)
        self.snaps.append(snap)

    def close(self):
        """Remove the files."""
        if self._dir is not None:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None


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

#: elements of a tensor a follow works on at a time: its float64 working
#: copies are of a piece (16 MB), not of a tensor (1 GB of a 504M-parameter
#: model's) and never of the whole model
PIECE = 1 << 21


def _by_tensor(tree) -> Dict[str, np.ndarray]:
    """The tree's arrays on the host, by name, each in C order: the TPU
    hands a matrix back column-major, and to flatten that is a copy of
    the whole of it for every piece."""
    return {name: np.ascontiguousarray(a) for name, a
            in named_flatten(jax.device_get(tree))[0].items()}


def _pieces(lay) -> List[Tuple[str, int, int]]:
    """(tensor's name, lo, hi): every tensor of the layout, in the order
    ``_by_tensor`` names them, its elements cut into runs of ``PIECE``."""
    names = named_flatten(jax.eval_shape(
        lay.unflatten, jax.ShapeDtypeStruct((lay.total,), np.float32)))[0]
    return [(n, lo, min(lo + PIECE, lay.sizes[n])) for n in names
            for lo in range(0, max(lay.sizes[n], 1), PIECE)]


def _cut(by_name, piece) -> np.ndarray:
    """``piece`` of the flattened tensor it names."""
    name, lo, hi = piece
    return np.reshape(by_name[name], -1)[lo:hi]


def _stored(lay, file: _File) -> Callable[[Tuple[str, int, int]], np.ndarray]:
    """Reads a piece of the flat array a ``Follower`` wrote to ``file``,
    when it is asked for."""
    def read(piece):
        name, lo, hi = piece
        return file.read(lay.offsets[name] + lo, lay.offsets[name] + hi)

    return read


def _same_tensors(grads, pieces):
    names = {name for name, _, _ in pieces}
    if set(grads) != names:
        raise cells.CellError(
            f"the reference returns gradients for {sorted(grads)}, the model "
            f"has {sorted(names)}")


def _follow_dense(f: Follower, batch_at):
    lay, recipe, snaps, k = f.arm.setup.layout, f.arm.recipe, f.snaps, f.k
    steps = k * (len(snaps) - 1)
    pieces = _pieces(lay)
    p0 = _stored(lay, snaps[0]["params"])
    decayed = _decayed(recipe, [name for name, _, _ in pieces])
    wd, m = recipe["weight_decay"], recipe["momentum"]
    grad = _loss_and_grads(f.reference)

    # the reference's own trajectory, float32 on the default device
    params = lay.unflatten(snaps[0]["params"].whole())
    buf, ref_losses, g0 = None, [], None
    for t in range(steps):
        loss, g = grad(params, *batch_at(t % k))
        ref_losses.append(float(loss))
        if t == 0:
            g0 = _by_tensor(g)
            _same_tensors(g0, pieces)
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
        # the first gradient as the optimizer got it: its momentum buffer
        # after one step, or the parameters' change where there is none
        after_one = _stored(lay, snaps[1]["momentum" if m else "params"])
        lr0 = float(recipe["lr"](0))

        def first_gradient():
            for piece in pieces:
                p = p0(piece)
                d1 = after_one(piece) if m else (p - after_one(piece)) / lr0
                yield (piece, d1 - (wd * p if decayed[piece[0]] else 0.0),
                       _cut(g0, piece))

        out["grad_rel_err"] = _worst(first_gradient())
    p_end = _stored(lay, snaps[-1]["params"])

    def change():
        for piece in pieces:
            p = p0(piece)
            yield piece, p_end(piece) - p, _cut(p_ref, piece) - p

    out["update_norm_gap"] = _summary(_leafwise(change())["norm_gap"])
    return out


def _follow_dgc(f: Follower, batch_at):
    arm, snaps, k = f.arm, f.snaps, f.k
    lay, recipe, engine = arm.setup.layout, arm.recipe, arm.setup.engine
    mem_cfg = arm.dist.compressor.memory
    if getattr(mem_cfg, "gradient_clipping", None) is not None:
        raise cells.CellError(
            "model check: the dgc arm clips its gradients, which the plain "
            "reference does not; a configuration with a reference states "
            "no clipping")
    grad = _loss_and_grads(f.reference)
    wd, m_opt = recipe["weight_decay"], recipe["momentum"]
    pieces = _pieces(lay)
    decayed = _decayed(recipe, [name for name, _, _ in pieces])

    def mean_memory(snap):
        """Reads a piece of the workers' mean canonical momentum or
        velocity: each worker's view in float32 as the engine gives it,
        the mean taken in float64 when a piece is asked for."""
        fulls = []
        for w in range(arm.world):
            full = jax.device_get(engine.memory_full({
                name[len("memory."):]: file.row(w)
                for name, file in snap.items()
                if name.startswith("memory.")}))
            fulls.append({key: _by_tensor(lay.unflatten(v))
                          for key, v in full.items()})

        def mean(key, piece):
            total = 0.0
            for full in fulls:
                total = total + np.asarray(_cut(full[key], piece),
                                           np.float64)
            return total / arm.world

        return mean

    losses, ref_losses, conserved = [], [], []
    buf = {}     # dgc_sgd's momentum buffer: all a follow carries onward
    mem_next = mean_memory(snaps[0]) if k == 1 else None
    for d in range(len(snaps) - 1):
        before, after = snaps[d], snaps[d + 1]
        loss, g = grad(lay.unflatten(before["params"].whole()),
                       *batch_at(0))
        losses.append(float(np.ravel(after["losses"])[0]))
        ref_losses.append(float(loss))
        if k != 1:
            continue
        g = _by_tensor(g)
        _same_tensors(g, pieces)
        p, p_next = (_stored(lay, before["params"]),
                     _stored(lay, after["params"]))
        # the step before's is dropped before this step's is made: three
        # of them at once are 24 bytes a parameter
        mem, mem_next = mem_next, None
        mem_next = mean_memory(after)
        lr = float(recipe["lr"](d))

        def reached_or_stayed_and_compensated():
            for piece in pieces:
                held, here = decayed[piece[0]], p(piece)
                # dgc_sgd: momentum runs over the weight-decay term alone
                term = wd * here if held else 0.0 * here
                if wd and m_opt:
                    buf[piece] = term if piece not in buf else (
                        m_opt * buf[piece]
                        + (1 - recipe["dampening"]) * term)
                    if held:
                        term = (term + m_opt * buf[piece]
                                if recipe["nesterov"] else buf[piece])
                applied = (here.astype(np.float64) - p_next(piece)) / lr \
                    - term
                _, want = exchange_reference.momentum_correction(
                    mem("momentums", piece), mem("velocities", piece),
                    _cut(g, piece), mem_cfg.momentum, mem_cfg.nesterov)
                yield piece, applied + mem_next("velocities", piece), want

        conserved.append(_worst(reached_or_stayed_and_compensated()))
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


def _leafwise(triples) -> Dict[str, Dict[str, float]]:
    """Per tensor: the norm of the difference, and the gap between the
    norms, over max(the reference's norm, its median tensor's norm).
    ``triples``: (a tensor's name or a (name, lo, hi) piece of it, the
    program's array, the reference's), a tensor's pieces one after the
    other; the float64 copies are of one triple at a time."""
    squares: Dict[str, List[float]] = {}
    for key, got, want in triples:
        want = np.asarray(want, np.float64).ravel()
        got = np.asarray(got, np.float64).ravel()
        sums = squares.setdefault(key[0] if isinstance(key, tuple) else key,
                                  [0.0, 0.0, 0.0])
        for i, x in enumerate((want, got, got - want)):
            sums[i] += float(x.dot(x))
    norms = {n: math.sqrt(sums[0]) for n, sums in squares.items()}
    floor = statistics.median(norms.values())
    err, gap = {}, {}
    for n, (_, got, diff) in squares.items():
        scale = max(norms[n], floor)
        if scale == 0.0:                      # every reference tensor is 0
            scale = 1.0
        err[n] = math.sqrt(diff) / scale
        gap[n] = abs(math.sqrt(got) - norms[n]) / scale
    return {"rel_err": err, "norm_gap": gap}


def _worst(triples) -> Dict[str, Any]:
    both = _leafwise(triples)
    out = _summary(both["rel_err"])
    out["norm_gap_max"] = max(both["norm_gap"].values(), key=_nan_first)
    return out


def _losses(got, want) -> Dict[str, Any]:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want) / np.abs(want)
    return {"max": float(max(err, key=_nan_first)),
            "program": got.tolist(), "reference": want.tolist()}
