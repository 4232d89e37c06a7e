"""Driver-runnable on-TPU regression check (VERDICT r2 item 5 / SURVEY §7
item 6 bit-compatibility contract).

CPU pytest runs the Pallas kernels in interpret mode and approx_max_k
lowers to an exact sort there, so CI cannot catch a Mosaic compilation or
recall regression. This script runs ON THE REAL CHIP and asserts:

1. compiled-Pallas == jnp reference (bitwise) for fused_compensate,
   fused_compensate_masked, fused_compensate_bits (the shipped bit-packed
   transmit record, incl. the half-group layout and the bf16 state form),
   ladder_counts, topk_rows, the segment-top-2 candidate kernels, and the
   four opt-in kernels (select_pack_rows incl. its multi-round form,
   payload_apply_bits, dgc_forward_rows, dgc_apply_rows) at the engine's
   ResNet-50 operating shapes, and payload_update_bits with the
   optimizer's rule == payload_apply_bits + the optimizer at VGG-16-BN's
   and at ResNet-50's T, each with its configuration's constants;
2. approx-selection recall >= 0.95 at every ResNet-50 approx bucket
   (exact top-k reference computed on the same device).

Prints ONE JSON line like bench.py:
{"metric": "tpu_regression_check", "value": 1|0, "unit": "pass",
 "kernels": {...}, "recall": {...}} — value 1 means every check passed.

Usage: python scripts/tpu_check.py
"""

import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np


def check_kernels():
    """Compiled vs interpret equality at engine shapes. Returns
    {name: bool}."""
    from dgc_tpu.ops import kernels
    from dgc_tpu.utils.device import require_tpu

    require_tpu("tpu_check")
    rng = np.random.RandomState(0)
    out = {}

    # fused compensate at a [T]-scale but CI-friendly size (shape doesn't
    # change the kernel's grid logic beyond chunk count; 2M spans >1 chunk)
    n = 2_097_152 + 4096
    g = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(rng.randn(n), jnp.float32)
    v = jnp.asarray(rng.randn(n), jnp.float32)
    sent = jnp.asarray((rng.rand(n) < 0.001).astype(np.float32))

    cm, cv = kernels.fused_compensate(g, m, v, 0.9, False)
    rm, rv = kernels.fused_compensate_reference(g, m, v, 0.9, False)
    out["fused_compensate"] = bool(
        np.array_equal(np.asarray(cm), np.asarray(rm))
        and np.array_equal(np.asarray(cv), np.asarray(rv)))

    cm, cv = kernels.fused_compensate_masked(g, m, v, sent, 0.9, True, True)
    rm, rv = kernels.fused_compensate_masked_reference(
        g, m, v, sent, 0.9, True, True)
    out["fused_compensate_masked"] = bool(
        np.array_equal(np.asarray(cm), np.asarray(rm))
        and np.array_equal(np.asarray(cv), np.asarray(rv)))

    # bf16 error-feedback state (configs/dgc/bf16mem.py): mixed-dtype
    # blocks (f32 grad/sent, bf16 state) must compile under Mosaic and
    # match the f32-math-one-rounding reference bitwise. Deliberately
    # UNALIGNED length: exercises the 16-sublane pad branch the engine's
    # aligned buffers skip (the one TPU-specific code path CPU pytest
    # cannot validate).
    nb = n + 4097
    gb = jnp.asarray(rng.randn(nb), jnp.float32)
    sb = jnp.asarray((rng.rand(nb) < 0.001).astype(np.float32))
    mb = jnp.asarray(rng.randn(nb), jnp.bfloat16)
    vb = jnp.asarray(rng.randn(nb), jnp.bfloat16)
    cm, cv = kernels.fused_compensate(gb, mb, vb, 0.9, False)
    rm, rv = kernels.fused_compensate_reference(gb, mb, vb, 0.9, False)
    out["fused_compensate_bf16"] = bool(
        np.array_equal(np.asarray(cm, np.float32),
                       np.asarray(rm, np.float32))
        and np.array_equal(np.asarray(cv, np.float32),
                           np.asarray(rv, np.float32)))
    cm, cv = kernels.fused_compensate_masked(gb, mb, vb, sb, 0.9, True,
                                             True)
    rm, rv = kernels.fused_compensate_masked_reference(
        gb, mb, vb, sb, 0.9, True, True)
    out["fused_compensate_masked_bf16"] = bool(
        np.array_equal(np.asarray(cm, np.float32),
                       np.asarray(rm, np.float32))
        and np.array_equal(np.asarray(cv, np.float32),
                           np.asarray(rv, np.float32)))

    # bit-packed transmit record (the engine's shipped masking path):
    # compiled expansion must match the jnp unpack reference bitwise, in
    # both the aligned and the half-group (n % 4096 == 2048) layouts,
    # and in the mixed-dtype bf16-state form
    for label, nn in (("", n), ("_halfgroup", n + 2048)):
        idxs = jnp.asarray(rng.choice(nn, 25_533, replace=False)
                           .astype(np.int32))
        bits = kernels.pack_sent_bits(idxs, nn)
        gg = jnp.asarray(rng.randn(nn), jnp.float32)
        mm = jnp.asarray(rng.randn(nn), jnp.float32)
        vv = jnp.asarray(rng.randn(nn), jnp.float32)
        cm, cv = kernels.fused_compensate_bits(gg, mm, vv, bits, 0.9,
                                               True, True)
        rm, rv = kernels.fused_compensate_bits_reference(
            gg, mm, vv, bits, 0.9, True, True)
        out[f"fused_compensate_bits{label}"] = bool(
            np.array_equal(np.asarray(cm), np.asarray(rm))
            and np.array_equal(np.asarray(cv), np.asarray(rv)))
    bitsb = kernels.pack_sent_bits(
        jnp.asarray(rng.choice(n, 25_533, replace=False).astype(np.int32)),
        n)
    cm, cv = kernels.fused_compensate_bits(g, mb[:n], vb[:n], bitsb, 0.9,
                                           True, True)
    rm, rv = kernels.fused_compensate_bits_reference(
        g, mb[:n], vb[:n], bitsb, 0.9, True, True)
    out["fused_compensate_bits_bf16"] = bool(
        np.array_equal(np.asarray(cm, np.float32),
                       np.asarray(rm, np.float32))
        and np.array_equal(np.asarray(cv, np.float32),
                           np.asarray(rv, np.float32)))

    # ladder counts at a ResNet-50 bucket shape (rows unpadded: the kernel
    # pads in-trace)
    imp = jnp.asarray(np.abs(rng.randn(17, 262144)).astype(np.float32))
    thr = jnp.asarray(np.quantile(np.asarray(imp), 0.999, axis=1),
                      jnp.float32)
    ck = kernels.ladder_counts(imp, thr, 0.8, 11)
    rk = kernels.ladder_counts_reference(imp, thr, 0.8, 11)
    out["ladder_counts"] = bool(np.array_equal(np.asarray(ck),
                                               np.asarray(rk)))

    # topk_rows at the gated operating point (k*cols < 2M -> kernel path)
    x = jnp.asarray(rng.randn(22, 36864), jnp.float32)
    cv_, ci_ = kernels.topk_rows(x, 37)
    rv_, ri_ = kernels.topk_rows_reference(x, 37)
    out["topk_rows"] = bool(
        np.array_equal(np.asarray(cv_), np.asarray(rv_))
        and np.array_equal(np.asarray(ci_), np.asarray(ri_)))

    # segment-top-2 candidates (the r5 selection kernel) at a ResNet-50
    # bucket geometry, base off zero so the BlockSpec offset arithmetic
    # is exercised
    span = kernels._SEG_BLOCKS * 128
    base, rows, cols = span * 3, 3, span * 72      # [3, 2.36M]
    vec = jnp.asarray(rng.randn(base + rows * cols + span), jnp.float32)
    v2d = vec.reshape(-1, 128)
    cvk, cck = kernels.seg_top2_candidates(v2d, base, rows, cols)
    cvr, ccr = kernels.seg_top2_reference(v2d, base, rows, cols)
    out["seg_top2_candidates"] = bool(
        np.array_equal(np.asarray(cvk), np.asarray(cvr))
        and np.array_equal(np.asarray(cck), np.asarray(ccr)))

    # fused compensate+candidates (the r5 final engine path): state
    # bitwise the plain bits kernel AND candidates bitwise the reference
    # composition, with a grad buffer LONGER than the state (the no-slice
    # engine calling convention) and a tail past the last whole segment
    nf = span * 16 + 2048
    gf = jnp.asarray(rng.randn(nf + 4096), jnp.float32)
    mf = jnp.asarray(rng.randn(nf), jnp.float32)
    vf = jnp.asarray(rng.randn(nf), jnp.float32)
    bitsf = kernels.pack_sent_bits(
        jnp.asarray(rng.choice(nf, 8192, replace=False).astype(np.int32)),
        nf)
    cm, cv2, ccv, cci = kernels.fused_compensate_bits_cands(
        gf, mf, vf, bitsf, 0.9, False, True)
    rm, rv2, rcv, rci = kernels.fused_compensate_bits_cands_reference(
        gf, mf, vf, bitsf, 0.9, False, True)
    nseg = nf // span
    out["fused_compensate_bits_cands"] = bool(
        np.array_equal(np.asarray(cm), np.asarray(rm))
        and np.array_equal(np.asarray(cv2), np.asarray(rv2))
        and np.array_equal(np.asarray(ccv)[:nseg], np.asarray(rcv))
        and np.array_equal(np.asarray(cci)[:nseg], np.asarray(rci)))

    # --- the opt-in kernels (fused_select / fused_apply / megakernel) at
    #     the ResNet-50 engine geometry, ratio 0.001: T = 27,068,416 with
    #     a 25,583-entry payload per worker; the exact-selection buckets
    #     the fused select owns on the TPU backend are [8, 16384] k=17
    #     and [11, 65536] k=66 ---
    def eq(got, want):
        return all(np.array_equal(np.asarray(g_), np.asarray(w_))
                   for g_, w_ in zip(got, want))

    def rows_with_tails(R, cols_):
        """Signed rows whose tails past ``numels`` are structural zeros
        (ParamLayout.flatten's invariant)."""
        x_ = rng.randn(R, cols_).astype(np.float32)
        numels = rng.randint(cols_ // 2, cols_ + 1, R).astype(np.int32)
        x_[np.arange(cols_)[None, :] >= numels[:, None]] = 0.0
        return jnp.asarray(x_), jnp.asarray(numels)

    for R, cols_, k in ((8, 16384, 17), (11, 65536, 66)):
        xs, ns = rows_with_tails(R, cols_)
        out[f"select_pack_rows_{R}x{cols_}_k{k}"] = eq(
            kernels.select_pack_rows(xs, ns, k),
            kernels.select_pack_rows_reference(xs, ns, k))
    # multi-round form (k > 128): the [11, 65536] bucket at the wm5
    # warm-up ratio 0.0032, the widest selection the TPU gate
    # (max_sel * cols <= 16M) admits there
    xs, ns = rows_with_tails(11, 65536)
    out["select_pack_rows_mr_11x65536_k210"] = eq(
        kernels.select_pack_rows(xs, ns, 210),
        kernels.select_pack_rows_reference(xs, ns, 210))

    # forward megakernel on the one ResNet-50 bucket it owns ([8, 16384]
    # at flat base 26,935,296 — NOT word-group aligned, so the per-row
    # funnel shifts of _realign_bits_rows run), at k=17 and at a
    # multi-lane-block k (the warm-up ratio 0.0316)
    T50, base50, R, cols_ = 27_068_416, 26_935_296, 8, 16384
    nreg = R * cols_
    bits50 = kernels.pack_sent_bits(
        jnp.asarray(rng.choice(T50, 25_583, replace=False)
                    .astype(np.int32)), T50)
    gr, mr, vr = (jnp.asarray(rng.randn(nreg), jnp.float32)
                  for _ in range(3))
    _, ns = rows_with_tails(R, cols_)
    for k in (17, 518):
        out[f"dgc_forward_rows_{R}x{cols_}_k{k}"] = eq(
            kernels.dgc_forward_rows(gr, mr, vr, bits50, base50,
                                     ns, k, 0.9, False, True),
            kernels.dgc_forward_rows_reference(gr, mr, vr, bits50, base50,
                                               ns, k, 0.9, False, True))

    # apply epilogues over the whole [T] buffer: one worker's payload,
    # unique coordinates (the bitwise contract; duplicates differ by
    # scatter order only), a random subset flagged as locally sent
    pidx = jnp.asarray(rng.choice(T50, 25_583, replace=False)
                       .astype(np.int32))
    pval = jnp.asarray(rng.randn(25_583), jnp.float32)
    pflag = jnp.asarray(rng.rand(25_583) < 0.5)
    out["payload_apply_bits"] = eq(
        jax.jit(lambda v_, i_, f_, d_: kernels.payload_apply_bits(
            v_, i_, f_, T50, bits_donor=d_))(pval, pidx, pflag, bits50),
        jax.jit(lambda v_, i_, f_: kernels.payload_apply_bits_reference(
            v_, i_, f_, T50))(pval, pidx, pflag))
    out["dgc_apply_rows"] = eq(
        jax.jit(lambda v_, i_, f_, d_: kernels.dgc_apply_rows(
            v_, i_, f_, T50, bits_donor=d_, divisor=4.0))(
                pval, pidx, pflag, bits50),
        jax.jit(lambda v_, i_, f_: kernels.dgc_apply_rows_reference(
            v_, i_, f_, T50, divisor=4.0))(pval, pidx, pflag))
    out.update(check_update_pass())
    out.update(check_pack_pass())
    return out


#: what :func:`check_update_pass` runs: (name, T, one worker's pairs,
#: the dense tail behind the block, nesterov, weight decay): the two
#: benchmark configurations' geometry (``PERF.md`` §4) and optimizer
#: constants (``configs/imagenet/*.py``; lr 0.0125 scheduled and
#: momentum 0.9 at both). Since PR 41 both compile ``path=update``.
UPDATE_PASS_CASES = (
    ("vggT", 139_028_480, 138_360, 69_632, False, 5e-5),
    ("resnet50T", 27_068_416, 25_583, 55_296, True, 1e-4),
)


def check_update_pass(cases=UPDATE_PASS_CASES):
    """``kernels.payload_update_bits`` with ``dgc_sgd``'s own rule against
    the two passes it replaces (``payload_apply_bits``, then the
    optimizer's ``update`` and the add, both compiled) at each of
    ``cases``: VGG-16-BN's and ResNet-50's T with the benchmark's
    constants, a weight-decay mask whose spans cut a chunk, one worker's
    pairs and four workers' with cross-worker duplicates: p', buf' and
    the transmit bits bitwise, the tail [T, P) as it came. The
    benchmark's exchange check does not run this form (``PERF.md``
    §7.1c), and XLA:CPU contracts the rule's FMAs differently in the two
    programs, so the chip is where this comparison holds with real
    constants. Returns {name: bool}."""
    from dgc_tpu.compression.flat import LayoutMask
    from dgc_tpu.optim import dgc_sgd

    out = {}
    for name, T, per, tail, nesterov, weight_decay in cases:
        P = T + tail
        mask = LayoutMask(P, jnp.int32, [(0, 1_000_000, True),
                                         (1_000_000, 1_000_512, False),
                                         (1_000_512, T, True), (T, P, False)])
        opt = dgc_sgd(lambda c: 0.0125 * (1 + c.astype(jnp.float32) * 1e-3),
                      momentum=0.9, nesterov=nesterov,
                      weight_decay=weight_decay, weight_decay_mask=mask)
        rng = np.random.RandomState(35)
        for W in (1, 4):
            out.update(_update_pass_case(
                f"payload_update_bits_{name}_w{W}", opt, T, P, per, W, rng))
    return out


def _update_pass_case(name, opt, T, P, per, W, rng):
    """One geometry and world size of :func:`check_update_pass`, with
    ``first`` on (count 0) and off."""
    from dgc_tpu.ops import kernels
    from dgc_tpu.optim.sgd import SGDState

    rule = opt.rule

    def two_passes(v, i, f, p, b, bits, count):
        acc, nbits = kernels.payload_apply_bits(
            v, i, f, T, bits_donor=bits, out_total=P, max_dup=W)
        inside = jnp.arange(P) < T          # the tail is undefined
        upd, state = opt.update(jnp.where(inside, acc, 0.0),
                                SGDState(count, b), p)
        return (jnp.where(inside, p + upd, p),
                jnp.where(inside, state.momentum_buffer, b), nbits)

    def one_pass(v, i, f, p, b, bits, count):
        state = SGDState(count, b)
        (new_p, new_b), nbits = kernels.payload_update_bits(
            v, i, f, T, rule.blocks(state, p), rule.step,
            rule.scalars(state), bits_donor=bits, max_dup=W)
        return new_p, new_b, nbits

    idx = np.stack([rng.permutation(np.unique(rng.randint(0, T, 2 * per)))
                    [:per] for _ in range(W)])
    shared = idx[0, :per // 10]                  # cross-worker duplicates
    for w in range(1, W):    # each once a worker: what ``max_dup`` is owed
        own = idx[w][~np.isin(idx[w], shared)]
        idx[w] = np.concatenate([shared, own])[:per]
    flags = np.zeros((W, per), bool)
    flags[W - 1] = True
    pairs = (jnp.asarray(rng.randn(W * per).astype(np.float32) / W),
             jnp.asarray(idx.reshape(-1).astype(np.int32)),
             jnp.asarray(flags.reshape(-1)))
    k = jax.random.split(jax.random.PRNGKey(W), 2)
    p = jax.random.normal(k[0], (P,), jnp.float32)
    b = jax.random.normal(k[1], (P,), jnp.float32) * 1e-3
    bits = jnp.zeros((kernels.num_sent_words(T),), jnp.int32)
    two_passes, one_pass = jax.jit(two_passes), jax.jit(one_pass)
    same = jax.jit(lambda want, got, p0: jnp.stack(
        [jnp.array_equal(x, y) for x, y in zip(want, got)]
        + [jnp.array_equal(got[0][T:], p0[T:])]).all())
    out = {}
    for count in (0, 3):                        # ``first`` and not
        want = two_passes(*pairs, p, b, bits, jnp.int32(count))
        got = one_pass(*pairs, p, b, bits, jnp.int32(count))
        out[f"{name}_count{count}"] = bool(same(want, got, p))
        del want, got
    return out


#: a ``PLACE_MIN_BYTES`` no tensor reaches: the pack concatenates
NEVER = 1 << 60


@contextlib.contextmanager
def place_min_bytes(bound):
    """``flat.PLACE_MIN_BYTES`` at ``bound`` for what is traced inside
    (the constant is read while ``ParamLayout.flatten`` is traced)."""
    from dgc_tpu.compression import flat as flat_mod
    shipped, flat_mod.PLACE_MIN_BYTES = flat_mod.PLACE_MIN_BYTES, bound
    try:
        yield
    finally:
        flat_mod.PLACE_MIN_BYTES = shipped


def dgc_layout(make):
    """(shapes of the model's parameters, its DGC compressor, layout)."""
    from dgc_tpu import DGCCompressor, DGCSGDMemory
    from dgc_tpu.compression.flat import ParamLayout
    from dgc_tpu.utils.pytree import named_flatten

    model = make()
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)),
        train=True))["params"]
    named, _ = named_flatten(shapes)
    comp = DGCCompressor(0.001, memory=DGCSGDMemory(momentum=0.9))
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    return shapes, comp, ParamLayout.for_compressor(shapes, comp)


def check_pack_pass(steps: int = 3):
    """The gradient pack's placement pass (``kernels.place_rows`` through
    ``ParamLayout.flatten``) against the concatenation it replaces,
    compiled, at VGG-16-BN's and ResNet-50's full DGC layouts: the flat
    buffer bitwise, with the constant as shipped (VGG places fc1 + fc2,
    478,150,656 B; ResNet-50 nothing) and lowered to 1 MiB (8 and 20
    tensors: convolution kernels through their 2-D view, slots at every
    tile-aligned base the layouts have); then VGG's whole DGC step,
    ``steps`` dispatches from one state, every array of the state
    bitwise the concatenate form's. Nothing that decides ``correct`` in
    the benchmark runs the step's pack (``PERF.md`` §7.1c). Returns
    {name: bool}."""
    from dgc_tpu.compression import flat as flat_mod
    from dgc_tpu.models import resnet50, vgg16_bn

    shipped = flat_mod.PLACE_MIN_BYTES
    out = {}

    def packed(layout, tree, bound):
        with place_min_bytes(bound):
            return jax.jit(
                lambda t: layout.flatten(t, place=True))(tree)

    same = jax.jit(jnp.array_equal)
    for make, at_shipped in ((vgg16_bn, 478_150_656), (resnet50, 0)):
        shapes, _, layout = dgc_layout(make)
        ok = layout.pack_bytes()["place"] == at_shipped
        leaves, treedef = jax.tree.flatten(shapes)
        keys = jax.random.split(jax.random.PRNGKey(43), len(leaves))
        tree = jax.tree.unflatten(treedef, [
            jax.random.normal(k, s.shape, jnp.float32)
            for k, s in zip(keys, leaves)])
        want = packed(layout, tree, NEVER)
        for name, bound in (("shipped", shipped), ("1MiB", 1 << 20)):
            got = packed(layout, tree, bound)
            out[f"pack_pass_{make.__name__}_{name}"] = ok and bool(
                same(want, got))
            del got
        del want, tree
    out[f"pack_pass_vgg16_bn_step_x{steps}"] = _pack_pass_steps(
        vgg16_bn, steps)
    return out


def _pack_pass_steps(make, steps):
    """``steps`` dispatches of ``make``'s flat DGC step (the benchmark's
    optimizer constants, the optimizer's rule offered, state donated) at
    the constant as shipped and out of reach: every array of the two
    final states bitwise equal, and the pack placed where it should."""
    from dgc_tpu import DistributedOptimizer, dgc_sgd
    from dgc_tpu.compression import flat as flat_mod
    from dgc_tpu.parallel import make_mesh
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)

    model = make()
    world = len(jax.devices())
    mesh = make_mesh(world)
    rng = np.random.RandomState(43)
    images = jnp.asarray(rng.randn(world * 8, 224, 224, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 1000, world * 8), jnp.int32)

    finals, placed = [], []
    for bound in (flat_mod.PLACE_MIN_BYTES, NEVER):
        with place_min_bytes(bound):
            v = dict(jax.jit(lambda k: model.init(
                k, jnp.zeros((1, 224, 224, 3)), train=True))(
                    jax.random.PRNGKey(0)))
            _, comp, _ = dgc_layout(make)
            dist = DistributedOptimizer(
                dgc_sgd(0.0125, momentum=0.9, weight_decay=5e-5), comp,
                world_size=world)
            setup = make_flat_setup(v, dist)
            placed.append(setup.layout.pack_bytes()["place"])
            state = shard_state(make_flat_state(v, dist, setup, world),
                                mesh, dist_opt=dist)
            del v
            # as ``benchmark/build.py`` builds it: VGG has dropout
            step = build_train_step(model.apply, dist, mesh, flat=setup,
                                    use_dropout=True, donate=True)
            for i in range(steps):
                state, metrics = step(state, images, labels,
                                      jax.random.PRNGKey(10 + i))
            finals.append((state, metrics["loss"]))
            del state, step
    got, want = (jax.tree.leaves(f) for f in finals)
    return (placed[0] > 0 and placed[1] == 0 and len(got) == len(want)
            and all(bool(jnp.array_equal(a, b)) for a, b in zip(got, want))
            and bool(jnp.isfinite(finals[0][1])))


def check_recall(threshold: float = 0.95):
    """Engine approx-selection recall at the ResNet-50 approx buckets.
    Returns {bucket: recall}."""
    from dgc_tpu import DGCCompressor, DGCSGDMemory
    from dgc_tpu.compression.flat import FlatDGCEngine, ParamLayout
    from dgc_tpu.models import resnet50
    from dgc_tpu.utils.pytree import named_flatten

    model = resnet50()
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)),
                   train=True)
    named, _ = named_flatten(v["params"])
    comp = DGCCompressor(0.001, memory=DGCSGDMemory(momentum=0.9))
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    layout = ParamLayout.for_compressor(v["params"], comp)
    engine = FlatDGCEngine(comp, layout)

    rng = np.random.RandomState(1)
    out = {}
    for bi, b in enumerate(engine.buckets):
        R, cols, k = b.rows, b.cols, b.max_sel
        if not (comp.approx_recall is not None
                and (k > 128 or k * cols > 2_000_000)):
            continue  # exact path
        x = jax.device_put(jnp.abs(jnp.asarray(
            rng.randn(R, cols), jnp.float32)))
        _, ai = jax.jit(lambda s: engine._select_topk(s, k))(x)
        _, ei = jax.jit(lambda s: jax.lax.top_k(s, k))(x)
        ai_n, ei_n = np.asarray(ai), np.asarray(ei)
        hits = [len(np.intersect1d(ai_n[r], ei_n[r])) / k for r in range(R)]
        out[f"bucket{bi}_{R}x{cols}_k{k}"] = round(float(np.mean(hits)), 4)
    return out


def check_recall_3d(threshold: float = 0.95):
    """Recall of the layout-free 3-D selection path at the VGG-16-BN fc
    buckets (the only model whose buckets pass the SEL3D gate): fraction
    of SELECTED coordinates that belong to the exact per-row top set.
    Returns {bucket: recall}."""
    from dgc_tpu import DGCCompressor, DGCSGDMemory, DistributedOptimizer, dgc_sgd
    from dgc_tpu.models import vgg16_bn
    from dgc_tpu.utils.pytree import named_flatten

    model = vgg16_bn()
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)),
                   train=True)
    named, _ = named_flatten(v["params"])
    comp = DGCCompressor(0.001, memory=DGCSGDMemory(momentum=0.9))
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=1)
    layout, engine = dist.make_flat(v["params"])
    rng = np.random.RandomState(3)
    out = {}
    for bi, b in enumerate(engine.buckets):
        if not (engine._use_seg_kernel(b) or engine._use_3d(b)):
            continue
        R, cols = b.rows, b.cols
        x = np.abs(rng.randn(R, cols)).astype(np.float32)
        # row tails beyond a tensor's numel are STRUCTURAL ZEROS in the
        # engine's flat buffer (ParamLayout.flatten) — the selection
        # paths rely on that invariant (zero candidates never beat a
        # positive threshold), so the driver must honor it
        for r in range(R):
            x[r, int(b.numels[r]):] = 0.0
        vec = np.zeros((layout.t_compressed,), np.float32)
        vec[b.base:b.base + R * cols] = x.reshape(-1)
        _, idx = jax.jit(
            lambda vv, kk, b=b: engine._sparsify_bucket_3d(
                vv, vv.reshape(-1, 128), b, kk))(
            jnp.asarray(vec), jax.random.PRNGKey(0))
        idx = np.asarray(idx)
        rec, fill = [], []
        for r in range(R):
            ns = int(b.num_selects[r])
            row = x[r][:int(b.numels[r])]
            got = set(int(i) for i in idx[r] if i != layout.sentinel)
            # ranking quality at the achieved size: the threshold cap can
            # legitimately select fewer than ns (the reference's payloads
            # are <= num_selects too, compression.py:151), so compare
            # against the exact top-|got| — and gate the fill separately
            # (the ladder guarantees ~lower_bound * ns passers)
            exact = set((int(b.row_offsets[r])
                         + np.argsort(-row)[:max(len(got), 1)]).tolist())
            rec.append(len(exact & got) / max(len(got), 1))
            fill.append(len(got) / ns)
        key = f"vgg3d_bucket{bi}_{R}x{cols}_k{b.max_sel}"
        out[key] = round(float(np.mean(rec)), 4)
        # quota fill rides the same >= threshold gate scaled by the
        # ladder's lower bound (0.8): report fill/0.8 so one pass/fail
        # rule covers both quantities
        out[key + "_fillx1.25"] = round(min(1.0, float(
            np.mean(fill) / 0.8)), 4)
    return out


def main():
    from dgc_tpu.utils import compile_cache

    compile_cache.enable()
    kernels_ok = check_kernels()
    recall = check_recall()
    recall.update(check_recall_3d())
    ok = all(kernels_ok.values()) and all(r >= 0.95 for r in recall.values())
    for name, good in kernels_ok.items():
        print(f"[kernel] {name}: {'OK (bitwise)' if good else 'MISMATCH'}",
              file=sys.stderr)
    for name, r in recall.items():
        print(f"[recall] {name}: {r}", file=sys.stderr)
    print(json.dumps({
        "metric": "tpu_regression_check",
        "value": 1 if ok else 0,
        "unit": "pass",
        "kernels": kernels_ok,
        "recall": recall,
    }))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
