"""Correctness of the timed STEP, against the configuration's plain
reference of its model.

What is compared is what the window then drives: set-up builds each arm's
compiled step and state once, drives them from the seed through three
dispatches (``run.py``: the first and its ``SOLO_WARMUP_STEPS``) of the
window's own call (``ArmRun.dispatch``) on the cell's own batch under the
cell's mesh, and hands the same objects to the window. A ``Follower``
copies what the follow will read of the arm's state (and no more) before
the first dispatch and after each of the first ``FOLLOWED`` (two: the first
runs from empty memory and an empty buffer, the second with them, and a
third is of the second's kind), into files under the process's temporary
directory, so that the host holds none of it through the window; nothing
else of the program is read. After the window has closed, the device peak
has been read and the arms' states are freed, the reference follows, and
reads the files back a piece of a tensor at a time.

EVERY followed step of EITHER arm is taken from the program's own state
before it: the reference's ``loss_and_grads`` (plain ``jax.numpy``,
float32, matmuls at ``highest``) is only ever called at parameters the
program held. An independent trajectory from the seed's weights would
carry one float32 rounding of its first step through every later forward
and backward pass, and a discontinuous layer (a top-k router) or a step
that is not yet stable multiplies it: a limit on such a number is loose
enough to mean nothing or refuses sound runs on some seeds (PERF.md
section 6, PR 40).

``dense`` arm, every followed step ``d``: the reference's loss and
gradient ``g`` at the program's parameters ``p_d``, and a plain SGD rule
written here (torch semantics: ``s = g + wd*p``, ``buf = m*buf + s``,
nesterov ``s + m*buf``; the first step's buffer is ``s`` itself) fed the
program's own momentum buffer ``b_d``, in float32 as an SGD forms it, so
that the rounding of the sum is common to both. Compared, worst step and
worst tensor: the loss; the gradient as the optimizer got it, worked out
from the buffers round the step (``b_{d+1} - m*b_d - wd*p_d``; from the
parameters' change where there is no momentum); the norm of the
parameters' change ``p_{d+1} - p_d`` against the rule's.

``dgc`` arm, every followed step: which coordinates DGC sends is the
engine's (approximate) choice, so the step is held to DGC's conservation,
split by the transmit record the step leaves (``memory.sent_bits``, a
worker's): the reference's momentum correction
(``benchmark/reference.py``) ``c`` of the workers' mean memory before the
step with the reference's gradient at those parameters is, coordinate by
coordinate, what stayed behind (the workers' mean velocity after the step)
plus what reached the parameters.

* *what stayed* (``conserved_rel_err``): on the coordinates NO worker
  sent, the workers' mean velocity after the step against ``c``. Both
  are state; nothing is divided by the learning rate, so the number
  reads the step's precision as the dense arm's gradient does.
* *what reached the parameters* (``unexplained_coords``, a count, limit
  0): over EVERY coordinate, the float64 prediction ``p - lr * ((c - the
  velocity left) + dgc_sgd's weight-decay term)`` against the program's
  next parameters. A coordinate is explained when the two differ by no
  more than ``APPLIED_ULPS`` float32 ulps of the parameter (the final
  add rounds to half of one) plus ``lr * COORD_FACTOR * GRAD_RTOL *
  max(|c|, the largest coordinate of the tensor's gradient)``: room for
  the float32 gradient's own error at ONE coordinate. A coordinate is a
  sum of many terms that may cancel (an embedding row's over a frequent
  token's occurrences), and its error is of the size of the terms, which
  its own value does not show and the tensor's largest coordinate does.
  WHAT THE COUNT SEES: a payload entry ``v`` (a worker's, so ``v /
  world`` of the mean) that is dropped, doubled or applied beside its
  index leaves the parameter ``lr * |v| / world`` from the prediction,
  and is counted where that exceeds the allowance: ``|v| / world >
  APPLIED_ULPS * ulp(p) / lr + COORD_FACTOR * GRAD_RTOL * max(|c|,
  largest)``. At a language model's learning rate the first term alone
  is 3.7e-7 (2 ulps of a parameter of 0.02 over 1e-2). The smallest entry
  the two followed steps send read 1.0e-4 and 2.7e-4 at ``wide_moe``
  (505M parameters), 270 times that, and zeroed before apply it was
  counted; a later step of a trained model, or a model whose gradients
  are smaller, sends entries under it. So the count sees a step's large
  entries (and any unsent coordinate that moved by more than its weight
  decay) and is not held to see its small ones; those are the exchange
  check's, whose ``exchange.unconserved_coords`` compares the exchange's
  own output exactly, entry by entry (``benchmark/check.py``).
  ``most_share`` in the event is the most of the gradient's share any
  coordinate used, in the units of ``COORD_FACTOR`` (a sound run stays
  under it: 0.017 at ``wide_lm``, 0.23 at ``wide_moe``; with no share at
  all the farthest coordinate of a 505M-parameter run lies 8e32 ulps out,
  a parameter at 0 having no ulp to speak of), beside ``most_ulps``, the
  farthest coordinate's ulps with its share taken off.

Compared as well: the loss at the first step of every followed dispatch.

A loop of kind ``scan`` runs ``k`` steps in a dispatch and leaves no
state after one step: there the reference runs the ``k`` steps of a
dispatch from the program's state at its start, and the losses and the
dense arm's parameter change over the dispatch are compared, no gradient.

Tensor by tensor, an error is the norm of the difference over the larger
of the reference's norm of that tensor and of the median tensor (some
gradients are all but zero, an unreached expert's exactly); the gap
between the two norms can be no larger. The reference module states the
limits that differ by model, with the readings each was set from:
``LOSS_RTOL``, ``GRAD_RTOL`` and ``CONSERVED_RTOL`` (the step's
precision), ``UPDATE_RTOL`` (the optimizer's rule). The count's allowance
(the exchange's bookkeeping) is ``APPLIED_ULPS`` and ``COORD_FACTOR``
here: the first is the add's and no model's, the second scales the
module's own ``GRAD_RTOL``.
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


#: dispatches of an arm a follow covers: the first, from empty memory and an
#: empty momentum buffer, and the second, with them; a third is of the
#: second's kind, and each costs the followers' files 20 B a parameter
FOLLOWED = 2
#: float32 ulps of a parameter its next value may lie from the float64
#: prediction before the coordinate counts as unexplained: the step's final
#: add rounds to half an ulp, and the prediction's own terms (float32
#: products by the learning rate and the weight decay) to less than
#: another; every sound run of the four fixtures reads 0.30 to 0.50 for the
#: farthest coordinate. The same for every model: it is the add's
APPLIED_ULPS = 2.0
#: the gradient's share of that allowance at one coordinate, in units of
#: ``GRAD_RTOL`` times the larger of the coordinate and the tensor's
#: largest. No derivation gives the 8. ``GRAD_RTOL`` bounds the NORM of a
#: tensor's error and says nothing of one coordinate: as first written (6,
#: the largest of 10^8 normal errors in standard deviations, times the
#: tensor's ROOT MEAN SQUARE) two of the first three sound seeds at
#: ``wide_moe`` counted embedding coordinates 2.1 and 4.8 ulps outside,
#: because an embedding's mean square is its unused rows' and its errors
#: sit in the used ones. With the tensor's largest coordinate in its place
#: (323 times the rms there) and 8 for 6, every sound run since counts 0:
#: those two seeds again and thirteen fresh ones on the chip, 38 on the
#: CPU. The form was fitted to two failures, so only the fresh seeds speak
#: for it. What it buys is paid in sight: see the module docstring, "what
#: the count sees"
COORD_FACTOR = 8.0


class Follower:
    """What a follow reads of one arm's state round its first dispatches,
    in files of its own directory under the temporary one until
    ``compare`` reads them back. ``snapshots``: how many there will be,
    one before the first dispatch and one after each followed one;
    further calls of ``snapshot`` keep nothing."""

    def __init__(self, cell, arm):
        ref = cell.config["reference"]
        self.reference = None if ref is None else cells.load_reference(ref)
        self.arm, self.snapshots = arm, FOLLOWED + 1
        # steps in a dispatch: a loop of kind ``scan`` leaves no state
        # after one step, so no gradient is read there
        self.k = cell.traffic["k"] if cell.traffic["loop"] == "scan" else 1
        self.snaps = []
        self._dir = None

    def reads(self, index: int) -> Tuple[str, ...]:
        """The parts of the state the follow reads of snapshot ``index``.
        ``_follow_dgc``: the parameters and the engine's memory round
        every step (the parameters before each dispatch where ``k`` steps
        run in one). ``_follow_dense``: the parameters round every
        dispatch; the momentum buffer before every dispatch but the first
        (it is empty there) and, where a dispatch is one step, after the
        last, which holds that step's gradient."""
        last = self.snapshots - 1
        if self.arm.name == "dgc":
            if self.k == 1:
                return ("params", "memory")
            return ("params",) if index < last else ()
        if (self.arm.recipe["momentum"] and index > 0
                and (self.k == 1 or index < last)):
            return ("params", "momentum")
        return ("params",)

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
        configuration without a reference copies nothing, and neither
        does a dispatch after the last followed one."""
        if self.reference is None or len(self.snaps) == self.snapshots:
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

    def written_bytes(self) -> int:
        """Bytes of the files that are there: what ``kept_bytes`` foretold,
        read back from the directory (the ``model_check`` event's
        ``followers_bytes``)."""
        if self._dir is None:
            return 0
        return sum(entry.stat().st_size for entry in os.scandir(self._dir))

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
              "conserved_rel_err": ref.CONSERVED_RTOL,
              "unexplained_coords": 0}
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
#: elements of a piece the arithmetic runs over at a time. A follow makes
#: some forty numpy passes over every coordinate, each into a temporary of
#: its own; a temporary of a whole piece (8 or 16 MB) is fresh memory from
#: the system every time, and its page faults cost 3.6 times the arithmetic
#: on the chip's host (57 ns a coordinate against 15.7 in blocks, my host
#: probe, PR 40), where a block's (256 or 512 KB) comes off the heap's free
#: list and stays in the core's cache. A piece stays what is READ at a
#: time, from a file or off the device
BLOCK = 1 << 16


def _flat(lay, tree) -> np.ndarray:
    """The tree as the layout's flat [P] array on the host, packed on the
    device by the program's own ``ParamLayout.flatten``: the TPU hands a
    MATRIX back column-major, and to bring a model's back tensor by tensor
    is a copy of each into C order (27 s of the 160 a 504M-parameter
    follow took, PERF.md section 6, PR 40)."""
    return np.asarray(jax.device_get(jax.jit(lay.flatten)(tree)))


def _pieces(lay) -> List[Tuple[str, int, int]]:
    """(tensor's name, lo, hi): every tensor of the layout, in the tree's
    order, its elements cut into runs of ``PIECE``."""
    names = named_flatten(jax.eval_shape(
        lay.unflatten, jax.ShapeDtypeStruct((lay.total,), np.float32)))[0]
    return [(n, lo, min(lo + PIECE, lay.sizes[n])) for n in names
            for lo in range(0, max(lay.sizes[n], 1), PIECE)]


def _stored(lay, flat) -> Callable[[Tuple[str, int, int]], np.ndarray]:
    """Reads a piece of a flat [P] array: one on the host, or the
    ``_File`` a ``Follower`` wrote, read when it is asked for."""
    cut = flat.read if isinstance(flat, _File) else (
        lambda lo, hi: flat[lo:hi])

    def read(piece):
        name, lo, hi = piece
        return cut(lay.offsets[name] + lo, lay.offsets[name] + hi)

    return read


def _same_tensors(grads, pieces):
    """``grads``: the reference's gradients, a tree like the parameters'."""
    got = set(named_flatten(grads)[0])
    names = {name for name, _, _ in pieces}
    if got != names:
        raise cells.CellError(
            f"the reference returns gradients for {sorted(got)}, the model "
            f"has {sorted(names)}")


def _worst_step(steps: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Of one number read at every followed step, the step that reads
    worst, with every step's reading beside it."""
    worst = max(range(len(steps)), key=lambda d: _nan_first(steps[d]["max"]))
    return {**steps[worst], "step": worst,
            "by_step": [step["max"] for step in steps]}


def _follow_dense(f: Follower, batch_at):
    lay, recipe, snaps, k = f.arm.setup.layout, f.arm.recipe, f.snaps, f.k
    pieces = _pieces(lay)
    decayed = _decayed(recipe, [name for name, _, _ in pieces])
    wd, m, damp = (recipe["weight_decay"], recipe["momentum"],
                   recipe["dampening"])
    grad = _loss_and_grads(f.reference)

    ref_losses, gradients, changes = [], [], []
    for d in range(len(snaps) - 1):
        before, after = snaps[d], snaps[d + 1]
        # the rule from the program's own state before the dispatch,
        # float32 on the default device. The first step's buffer is the
        # step itself (torch's clone), not a decayed zero. What was read
        # whole for it stays on the host for the pieces below: a file is
        # read once (the host holds 8 B a parameter less here than in the
        # dgc arm's follow)
        p_host = before["params"].whole()
        b_host = before["momentum"].whole() if m and d else None
        params = lay.unflatten(p_host)
        buf = None if b_host is None else lay.unflatten(b_host)
        for i in range(k):
            loss, g = grad(params, *batch_at(i))
            ref_losses.append(float(loss))
            s = _named_map(lambda n, g_, p: g_ + wd * p if decayed[n] else g_,
                           g, params)
            if m:
                buf = s if buf is None else jax.tree.map(
                    lambda b, s_: m * b + (1 - damp) * s_, buf, s)
                s = (jax.tree.map(lambda s_, b: s_ + m * b, s, buf)
                     if recipe["nesterov"] else buf)
            lr = float(recipe["lr"](d * k + i))
            params = jax.tree.map(lambda p, s_: p - lr * s_, params, s)
        p_ref = _stored(lay, _flat(lay, params))
        if k == 1:
            _same_tensors(g, pieces)
            g = _stored(lay, _flat(lay, g))
        del params, buf, s
        p, p_next = _stored(lay, p_host), _stored(lay, after["params"])
        b = None if b_host is None else _stored(lay, b_host)
        b_next = _stored(lay, after["momentum"]) if m and k == 1 else None

        def gradient_and_change(piece):
            """The ``_squares`` of the step's gradient as the optimizer
            got it (from the momentum buffers round the step, or from
            the parameters' change where there is no momentum; none
            where ``k`` steps ran) and of the parameters' change, each
            against the reference's, over one reading of the piece."""
            here, after_, want = p(piece), p_next(piece), p_ref(piece)
            buf_ = None if b is None else b(piece)
            buf_next = None if b_next is None else b_next(piece)
            g_ = g(piece) if k == 1 else None
            grads, moved = _Squares(), _Squares()
            for at in _blocks(here.size):
                h = here[at].astype(np.float64)
                moved.add(after_[at] - h, want[at] - h)
                if k != 1:
                    continue
                if not m:
                    s = (h - after_[at]) / lr
                elif buf_ is None:
                    s = buf_next[at].astype(np.float64)
                else:
                    s = buf_next[at] - m * buf_[at].astype(np.float64)
                    if damp:
                        s /= 1 - damp
                if decayed[piece[0]]:
                    h *= wd
                    s -= h
                grads.add(s, g_[at])
            return piece, grads.sums, moved.sums

        both = list(map(gradient_and_change, pieces))
        if k == 1:
            gradients.append(_worst((piece, grads)
                                    for piece, grads, _ in both))
        changes.append(_summary(_leafwise(
            (piece, moved) for piece, _, moved in both)["norm_gap"]))

    losses = np.concatenate([np.ravel(s["losses"]) for s in snaps[1:]])
    out = {"steps": k * (len(snaps) - 1),
           "loss_rel_err": _losses(losses, ref_losses),
           "update_norm_gap": _worst_step(changes)}
    if gradients:
        out["grad_rel_err"] = _worst_step(gradients)
    return out


def sent_coordinates(bits: np.ndarray, total: int) -> np.ndarray:
    """[total] bool from a worker's packed transmit record (the engine's
    ``memory.sent_bits``, int32 words): coordinate ``c`` is bit ``(c //
    128) % 32`` of word ``(c // 4096) * 128 + c % 128``, set where the
    step sent it."""
    words = np.ascontiguousarray(bits).astype("<i4").view(np.uint8)
    by_lane = np.unpackbits(words.reshape(-1, 128, 4), axis=-1,
                            bitorder="little")          # [groups, lane, bit]
    return by_lane.transpose(0, 2, 1).reshape(-1)[:total].astype(bool)


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
    grad_rtol = f.reference.GRAD_RTOL
    T = int(engine.T)

    def memory_views(snap):
        """Reads a piece of every worker's canonical momentum or velocity,
        float32 as the engine gives them: a list, a worker each."""
        fulls = []
        for w in range(arm.world):
            full = jax.device_get(engine.memory_full({
                name[len("memory."):]: file.row(w)
                for name, file in snap.items()
                if name.startswith("memory.")}))
            fulls.append({key: _stored(lay, np.asarray(flat))
                          for key, flat in full.items()})
        return lambda key, piece: [full[key](piece) for full in fulls]

    def exchanged_by_any(snap):
        """Reads a piece of: which coordinates the step that left ``snap``
        exchanged, on any worker. Sparsely, by the workers' transmit
        records; the dense tail [T, P) and a bucket planned dense are
        all-reduced whole, and leave no velocity behind."""
        sent = np.ones((lay.total,), bool)
        sent[:T] = False
        for w in range(arm.world if T else 0):
            sent[:T] |= sent_coordinates(snap["memory.sent_bits"].row(w), T)
        for bucket, regime in zip(engine.buckets, engine.regimes):
            if regime == "dense":
                sent[bucket.base:bucket.base + bucket.rows * bucket.cols] \
                    = True
        return lambda piece: sent[lay.offsets[piece[0]] + piece[1]:
                                  lay.offsets[piece[0]] + piece[2]]

    losses, ref_losses, stayed, unexplained = [], [], [], []
    buf = {}     # dgc_sgd's momentum buffer: all a follow carries onward
    mem_next = memory_views(snaps[0]) if k == 1 else None
    for d in range(len(snaps) - 1):
        before, after = snaps[d], snaps[d + 1]
        loss, g = grad(lay.unflatten(before["params"].whole()),
                       *batch_at(0))
        losses.append(float(np.ravel(after["losses"])[0]))
        ref_losses.append(float(loss))
        if k != 1:
            continue
        _same_tensors(g, pieces)
        g = _flat(lay, g)
        largest = {name: _largest(
            g[lay.offsets[name]:lay.offsets[name] + lay.sizes[name]])
            for name in dict.fromkeys(n for n, _, _ in pieces)}
        g = _stored(lay, g)
        p, p_next = (_stored(lay, before["params"]),
                     _stored(lay, after["params"]))
        # the step before's is dropped before this step's is made: three
        # of them at once are 24 bytes a parameter
        mem, mem_next = mem_next, None
        mem_next = memory_views(after)
        sent = exchanged_by_any(after)
        lr = float(recipe["lr"](d))
        # per tensor: the coordinates outside the allowance, how many
        # ulps the farthest one lies beyond the gradient's share of it,
        # the most of that share any coordinate used, in the units of
        # ``COORD_FACTOR``, and the coordinates that stayed
        outside, farthest, shares, stays = (
            dict.fromkeys(largest, 0), dict.fromkeys(largest, 0.0),
            dict.fromkeys(largest, 0.0), dict.fromkeys(largest, 0))
        share = lr * COORD_FACTOR * grad_rtol
        first = d == 0

        def stayed_and_reached(piece):
            name = piece[0]
            held, here, after_ = decayed[name], p(piece), p_next(piece)
            g_, unsent = g(piece), ~sent(piece)
            u, v = mem("momentums", piece), mem("velocities", piece)
            v_next = mem_next("velocities", piece)
            if wd and m_opt and first:
                buf[piece] = np.empty(here.shape, np.float32)
            squares = _Squares()
            for at in _blocks(here.size):
                h, a = here[at], after_[at]
                # dgc_sgd: momentum runs over the weight-decay term alone
                term = wd * h if held else 0.0 * h
                if wd and m_opt:
                    kept = buf[piece][at]
                    kept[:] = term if first else (
                        m_opt * kept + (1 - recipe["dampening"]) * term)
                    if held:
                        term = (term + m_opt * kept
                                if recipe["nesterov"] else kept)
                _, want = exchange_reference.momentum_correction(
                    _mean([x[at] for x in u]), _mean([x[at] for x in v]),
                    g_[at], mem_cfg.momentum, mem_cfg.nesterov)
                left = _mean([x[at] for x in v_next])
                # what stayed, where no worker sent
                stay = unsent[at]
                stays[name] += int(np.sum(stay))
                squares.add(left * stay, want * stay)
                # what reached the parameters, in parameter space
                over = np.subtract(want, left, dtype=np.float64)
                over += term
                over *= lr
                np.subtract(h, over, out=over)              # the prediction
                np.subtract(over, a, out=over)
                np.abs(over, out=over)
                ulp = np.spacing(np.maximum(np.abs(h), np.abs(a)))
                allowed = share * np.maximum(np.abs(want), largest[name])
                used = np.divide(over - APPLIED_ULPS * ulp, allowed,
                                 out=np.full(over.shape, -np.inf),
                                 where=allowed > 0)
                shares[name] = max(shares[name],
                                   COORD_FACTOR * float(np.max(used)),
                                   key=_nan_first)
                over -= allowed
                over /= ulp
                # a NaN is outside
                outside[name] += int(np.sum(~(over <= APPLIED_ULPS)))
                farthest[name] = max(farthest[name], float(np.max(over)),
                                     key=_nan_first)
            return piece, squares.sums

        stayed.append({**_worst(map(stayed_and_reached, pieces)),
                       "coords": sum(stays.values())})
        if T and not stayed[-1]["coords"]:
            raise cells.CellError(
                f"model check: of the {T} coordinates of the dgc arm's "
                f"sparse tier none stayed unsent at followed step {d}, so "
                f"conserved_rel_err compared nothing")
        far = max(farthest, key=lambda n: _nan_first(farthest[n]))
        unexplained.append({"max": sum(outside.values()),
                            "worst_tensor": max(outside, key=outside.get),
                            "by_tensor": outside, "most_ulps": farthest[far],
                            "most_ulps_tensor": far,
                            "most_share": max(shares.values(),
                                              key=_nan_first)})
    out = {"steps": k * (len(snaps) - 1),
           "loss_rel_err": _losses(losses, ref_losses)}
    if stayed:
        # over how many coordinates each step's was taken
        out["conserved_rel_err"] = {
            **_worst_step(stayed),
            "coords": [step["coords"] for step in stayed]}
        # a count: all the followed steps' together; the farthest
        # coordinate of any step
        far = max(unexplained, key=lambda step: _nan_first(step["most_ulps"]))
        out["unexplained_coords"] = {
            **_worst_step(unexplained),
            "max": sum(step["max"] for step in unexplained),
            "most_ulps": far["most_ulps"],
            "most_ulps_tensor": far["most_ulps_tensor"],
            "most_share": max(
                (step["most_share"] for step in unexplained),
                key=_nan_first)}
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


def _squares(got, want) -> Tuple[float, float, float]:
    """The squared norms of the reference's array, of the program's and
    of their difference, in float64."""
    want = np.asarray(want, np.float64).ravel()
    got = np.asarray(got, np.float64).ravel()
    return tuple(float(x.dot(x)) for x in (want, got, got - want))


def _blocks(n: int) -> List[slice]:
    """[0, n) in runs of ``BLOCK``."""
    return [slice(lo, min(lo + BLOCK, n)) for lo in range(0, n, BLOCK)]


class _Squares:
    """The ``_squares`` of a piece, summed over its blocks."""

    def __init__(self):
        self.sums = (0.0, 0.0, 0.0)

    def add(self, got, want):
        self.sums = tuple(a + b for a, b in zip(self.sums,
                                                _squares(got, want)))


def _mean(arrays: List[np.ndarray]) -> np.ndarray:
    """The workers' mean in float64, of one worker's as of four's."""
    total = np.zeros(arrays[0].shape, np.float64)
    for array in arrays:
        total += array
    total /= len(arrays)
    return total


def _largest(x: np.ndarray) -> float:
    """max |x| with no copy of ``x``; 0 of an empty array, NaN of one
    that holds a NaN."""
    if not x.size:
        return 0.0
    return max(float(np.max(x)), -float(np.min(x)))


def _leafwise(triples) -> Dict[str, Dict[str, float]]:
    """Per tensor: the norm of the difference, and the gap between the
    norms, over max(the reference's norm, its median tensor's norm).
    ``triples``: (a tensor's name or a (name, lo, hi) piece of it, the
    program's array, the reference's) or, in place of the arrays, their
    ``_squares``; a tensor's pieces one after the other."""
    squares: Dict[str, List[float]] = {}
    for key, *arrays in triples:
        sums = squares.setdefault(key[0] if isinstance(key, tuple) else key,
                                  [0.0, 0.0, 0.0])
        for i, x in enumerate(arrays[0] if len(arrays) == 1
                              else _squares(*arrays)):
            sums[i] += x
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
