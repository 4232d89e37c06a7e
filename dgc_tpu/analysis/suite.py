"""The repo's standing contract suite (dgclint layer 2).

Pins the paper-level guarantees of the compiled flat train step on a tiny
Conv+BN+Dense model over 8 (fake) devices — the same geometry the tier-1
tests exercise:

* **one sparse exchange**: the plain DGC step lowers to exactly 2
  all-gathers (payload values + transmit records) and 2 all-reduces
  (dense tail + loss mean); the dense engine drops to 0 gathers.
* **telemetry rides free**: telemetry=True adds exactly ONE packed
  all-reduce (taps.pmean_stats); telemetry=False is byte-identical to a
  build that never mentioned telemetry.
* **fleet taps cost one gather**: fleet=True replaces the telemetry
  pmean with ONE packed all-gather (net vs the plain build: +1
  all-gather, +0 all-reduce); fleet=False is byte-identical to a
  telemetry build that never mentioned fleet.
* **donation aliases**: donate=True materializes input_output_alias for
  the state buffers (param 0 included); donate=False aliases nothing.
* **fused-apply epilogue is barrier-free**: kernels.payload_apply_bits
  lowers without optimization_barrier ops (PR 1's fused epilogue).
* **megakernels cost nothing off, no collectives on**: megakernel=False
  is byte-identical to a build that never mentioned the flag (neither
  fused kernel body lowers); megakernel=True changes per-bucket compute
  only — zero all-gather / all-reduce delta vs the plain build.
* **adaptive degradation rides the fleet gather**: adaptive=None on a
  fleet build is byte-identical to a fleet build that never mentioned
  adaptive (zero resilience/adaptive code lowers); adaptive=on adds ZERO
  collectives — the policy reads the already-gathered w_clock lane and
  masked payload tails keep the wire shapes static.
* **guards cost nothing when off, no syncs when on**: guards=None is
  byte-identical to a build that never mentioned guards (and lowers zero
  resilience/guard or resilience/preempt code); guards=on (+ checksum)
  adds ZERO collectives — the bad-worker verdict rides the existing loss
  all-reduce and the checksum words ride the existing index all-gather.
* **trace markers are free**: trace=off (default) is byte-identical to
  the plain build with no ``dgcph`` token in the compiled module;
  trace=on adds ZERO collectives while the ``dgcph.*`` phase markers
  land in compiled op metadata (what telemetry/attrib aggregates). The
  gradient pack's placement pass (``kernels.place_rows``), where the
  layout has a tensor for it, lowers under part ``fwd_bwd.pack``.
* **elastic restart is free when off**: elastic resharding is restore-
  time host code — a step whose batch geometry went through
  ``resolve_batch_geometry`` (identity) is byte-identical to the plain
  build, and no ``resilience/elastic`` code ever lowers into the step.
* **the exchange plan is the program**: for every planner regime family
  (dense / fp32 / int8 / int8+packed-idx), ``Plan.collectives()`` equals
  the lowered HLO's collective counts — the all-dense plan compiles the
  sparse path away to zero gathers (the planner's never-lose fallback is
  structural, not a runtime branch).
* **gossip is a plan-time opt-in with a static wire**: a build that
  never names a gossip plan is byte-identical to the plain build with
  zero compression/gossip code lowered; a gossip-planned build (ring or
  hypercube) lowers to exactly ``Plan.collectives()`` — the round
  classifier reweights what flows through the fixed value/index
  gathers, it never changes the collective shape.
* **cohort surgery is host-only**: importing resilience/surgery leaves
  the compiled step byte-identical to the plain build, and an ACTIVE
  coordinator with a published excise order adds ZERO collectives — the
  widened (preempt, verdict, target) agreement rides the existing
  agree_preempt host gather, never the traced step.
* **f32 end-to-end**: no f64 tensor type in any variant.
* **trace stability**: same-shape calls never retrace.
* **shard_state stays collective-free** (source contract): the
  multi-process assembly path uses jax.make_array_from_callback and never
  re-introduces multihost broadcasts (the gloo hang fixed in PR 2).

``run_contract_suite()`` returns ``(name, violations)`` pairs;
``python -m dgc_tpu.analysis --contracts`` gates on them.
"""

import os
from typing import Callable, List, Optional, Tuple

from dgc_tpu.analysis.contracts import Contract, RecompileGuard

__all__ = ["run_contract_suite", "build_fixture", "shard_state_source_check"]

#: calibrated on the 8-device CPU mesh; the counts are backend-agnostic
#: (they come from the lax-level program, not backend expansion)
FLAT_COLLECTIVES = {"all-gather": 2, "all-reduce": 2}
DENSE_COLLECTIVES = {"all-gather": 0, "all-reduce": 2}


def build_fixture(mesh=None, world: int = 8, compressor: str = "dgc",
                  compressor_kwargs=None, plan=None, head: int = 10,
                  **step_kwargs):
    """(state, step, setup, (images, labels, key)) on a tiny model.

    Mirrors tests/test_telemetry.py's ``flat_step_pair`` geometry; any
    ``build_train_step`` kwarg passes through (donate/telemetry/guards/
    ...; a ``guards`` config also seeds the state's guard counters), and
    ``compressor_kwargs`` augments the DGC compressor construction (e.g.
    ``{"checksum": True}``). ``plan`` is an exchange plan
    (``dgc_tpu.compression.planner``) threaded through
    ``make_flat_setup`` — the engine re-fits it to the fixture's bucket
    geometry. ``head`` is the width of the last layer: at 128 its
    kernel [8, 128] is one (8, 128) tile at the head of the compressed
    block, the smallest tensor the gradient pack can place."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn

    from dgc_tpu import (DGCCompressor, DGCSGDMemory, DistributedOptimizer,
                         NoneCompressor, dgc_sgd)
    from dgc_tpu.parallel import make_mesh
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)
    from dgc_tpu.utils.pytree import named_flatten

    if mesh is None:
        mesh = make_mesh(world)

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Conv(8, (3, 3))(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            x = nn.relu(x)
            return nn.Dense(head)(x.mean(axis=(1, 2)))

    model = M()
    v = dict(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3))))

    def apply_fn(variables, x, train=True, mutable=None, rngs=None):
        if mutable:  # dgclint: ok[tracer-branch] — mutable is a static collection list

            return model.apply(variables, x, train=train, mutable=mutable,
                               rngs=rngs)
        return model.apply(variables, x, train=train)

    if compressor == "dgc":
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                             **(compressor_kwargs or {}))
        named, _ = named_flatten(v["params"])
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    elif compressor == "none":
        comp = NoneCompressor()
    else:
        raise ValueError(f"unknown compressor {compressor!r}")
    dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                world_size=world)
    setup = make_flat_setup(v, dist, plan=plan)
    state = shard_state(
        make_flat_state(v, dist, setup, world,
                        guards=step_kwargs.get("guards"),
                        adaptive=step_kwargs.get("adaptive")),
        mesh, dist_opt=dist)
    step = build_train_step(apply_fn, dist, mesh, flat=setup, **step_kwargs)

    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(world * 4, 16, 16, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 10, world * 4), jnp.int32)
    return state, step, setup, (images, labels, jax.random.PRNGKey(1))


def _step_contract(name, state, step, inputs, **expects) -> Contract:
    images, labels, key = inputs
    return Contract(name, step,
                    args=(state, images, labels, key)).expects(**expects)


def shard_state_source_check(root: Optional[str] = None) -> List[str]:
    """Source contract for the gloo shard_state fix (PR 2): the
    multi-process state-assembly branch must build global arrays with
    ``jax.make_array_from_callback`` (collective-free) and must not call
    multihost broadcast/assert helpers — those deadlock heterogeneous
    gloo meshes during state assembly."""
    import ast

    root = root or os.getcwd()
    path = os.path.join(root, "dgc_tpu", "training", "state.py")
    with open(path, "r", encoding="utf-8") as f:
        tree = ast.parse(f.read())
    # identifiers only — the module's comments legitimately *discuss* the
    # broadcast helpers it must not call
    idents = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    idents |= {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    idents |= {a.name for n in ast.walk(tree)
               if isinstance(n, (ast.Import, ast.ImportFrom))
               for a in n.names}
    out = []
    if "make_array_from_callback" not in idents:
        out.append("training/state.py: make_array_from_callback missing — "
                   "the collective-free multi-process assembly path is gone")
    for banned in ("multihost_utils", "assert_equal", "broadcast_one_to_all",
                   "sync_global_devices"):
        if banned in idents:
            out.append(f"training/state.py: {banned!r} referenced — "
                       "state assembly must stay collective-free")
    return out


def run_contract_suite(mesh=None, log: Callable[[str], None] = None,
                       root: Optional[str] = None
                       ) -> List[Tuple[str, List[str]]]:
    """Run every standing contract; returns (name, violations) pairs."""
    import jax

    say = log or (lambda s: None)
    results: List[Tuple[str, List[str]]] = []

    def run(name, fn):
        say(f"contract: {name}")
        try:
            results.append((name, fn()))
        except Exception as e:      # build/lower failure is a violation too
            results.append((name, [f"errored: {type(e).__name__}: {e}"]))

    state, step_plain, setup, inputs = build_fixture(
        mesh, donate=False, telemetry=False)
    plain = _step_contract(
        "flat-step-one-sparse-exchange", state, step_plain, inputs,
        collectives=FLAT_COLLECTIVES, donation=[], no_f64=True)
    run(plain.name, plain.check)

    _, step_telem, _, _ = build_fixture(mesh, donate=False, telemetry=True)
    telem = _step_contract(
        "telemetry-on-exactly-one-pmean", state, step_telem, inputs,
        collectives_delta=(plain, {"all-reduce": 1, "all-gather": 0}),
        no_f64=True)
    run(telem.name, telem.check)

    # a build that never names telemetry= must produce the same bytes as
    # telemetry=False: proof the flag is Python-static, not a traced no-op
    _, step_default, _, _ = build_fixture(mesh, donate=False)
    off = _step_contract(
        "telemetry-off-compiles-away", state, step_plain, inputs,
        forbid_substrings=["telemetry"],
        identical_to=_step_contract("telemetry-never-built", state,
                                    step_default, inputs))
    run(off.name, off.check)

    # fleet dispersion taps (ISSUE 10): the fleet build REPLACES the
    # telemetry pmean with one packed all_gather carrying the per-worker
    # lanes, so against the PLAIN build the whole feature costs exactly
    # one extra collective (+1 all-gather, +0 all-reduce) — the "at most
    # one packed collective, zero host syncs" pin
    from dgc_tpu.parallel import make_mesh as _make_mesh
    from dgc_tpu.telemetry import fleet as _fleet
    _, step_fleet, _, _ = build_fixture(mesh, donate=False, telemetry=True,
                                        fleet=True)
    clock = _fleet.make_clock(0.0, mesh or _make_mesh(8), 8)
    images_f, labels_f, key_f = inputs
    fon = Contract(
        "fleet-on-one-packed-gather", step_fleet,
        args=(state, images_f, labels_f, key_f, clock)).expects(
        collectives_delta=(plain, {"all-gather": 1, "all-reduce": 0}),
        no_f64=True)
    run(fon.name, fon.check)

    # fleet=False must be byte-identical to a telemetry build that never
    # mentioned fleet, with zero fleet code lowered into it
    _, step_foff, _, _ = build_fixture(mesh, donate=False, telemetry=True,
                                       fleet=False)
    foff = _step_contract(
        "fleet-off-compiles-away", state, step_foff, inputs,
        forbid_substrings=["telemetry/fleet"],
        identical_to=_step_contract("fleet-never-built", state,
                                    step_telem, inputs))
    run(foff.name, foff.check)

    # straggler-adaptive exchange (ISSUE 13): adaptive=None on a fleet
    # build must be byte-identical to a fleet build that never mentioned
    # adaptive, and no resilience/adaptive code may lower into it
    _, step_aoff, _, _ = build_fixture(mesh, donate=False, telemetry=True,
                                       fleet=True, adaptive=None)
    aoff = Contract(
        "adaptive-off-compiles-away", step_aoff,
        args=(state, images_f, labels_f, key_f, clock)).expects(
        forbid_substrings=["resilience/adaptive"],
        identical_to=fon)
    run(aoff.name, aoff.check)

    # adaptive on: the policy reads the already-gathered w_clock lane and
    # the verdict feeds forward through the donated state, so the whole
    # feature adds ZERO collectives on top of the fleet build — masked
    # payload tails keep the wire shapes static (no recompiles either)
    from dgc_tpu.resilience.adaptive import AdaptiveConfig
    state_a, step_aon, _, _ = build_fixture(
        mesh, donate=False, telemetry=True, fleet=True,
        adaptive=AdaptiveConfig())
    aon = Contract(
        "adaptive-on-no-new-collectives", step_aon,
        args=(state_a, images_f, labels_f, key_f, clock)).expects(
        collectives_delta=(fon, {"all-gather": 0, "all-reduce": 0}),
        no_f64=True)
    run(aon.name, aon.check)

    # guards=None must be byte-identical to a build that never mentioned
    # guards (the resilience layer is Python-static), and the plain
    # program must lower zero guard/preempt code
    _, step_goff, _, _ = build_fixture(mesh, donate=False, telemetry=False,
                                       guards=None)
    goff = _step_contract(
        "guards-off-compiles-away", state, step_goff, inputs,
        forbid_substrings=["resilience/guard", "resilience/preempt"],
        identical_to=plain)
    run(goff.name, goff.check)

    # guards + checksum on: the skip verdict rides the packed loss
    # all-reduce and the checksum words ride the index all-gather, so the
    # collective count is UNCHANGED — zero extra host syncs or exchanges
    from dgc_tpu.resilience import GuardConfig
    state_g, step_gon, _, _ = build_fixture(
        mesh, donate=False, telemetry=False,
        guards=GuardConfig(spike_window=8),
        compressor_kwargs={"checksum": True})
    gon = _step_contract(
        "guards-on-no-new-collectives", state_g, step_gon, inputs,
        collectives_delta=(plain, {"all-reduce": 0, "all-gather": 0}),
        no_f64=True)
    run(gon.name, gon.check)

    # the dense engine has its own memory/opt-state geometry: lower it
    # against its own fixture state, not the DGC one (lowered here, with
    # the markers off: it is the trace contracts' dense baseline)
    state_d, step_dense, _, _ = build_fixture(mesh, compressor="none",
                                              donate=False)
    dense = _step_contract(
        "dense-engine-no-gathers", state_d, step_dense, inputs,
        collectives=DENSE_COLLECTIVES, no_f64=True)
    run(dense.name, dense.check)

    # trace markers: lowering a fresh build while the phase markers are
    # ENABLED must add zero collectives (named scopes are pure metadata)
    # and the dgcph tokens must actually reach the compiled op metadata
    # (markers live in compiled op_name=..., not default StableHLO — so
    # this pin reads compiled text). Lowering is lazy: check() must run
    # INSIDE the enable window. Both engines' builds pass through every
    # scope the step opens: the parts of ``update`` and of ``fwd_bwd``,
    # ``params_view``, ``plumbing``, and the dense engine's own ``dense``.
    # The DGC build takes the streamed apply pass (the chip's default,
    # off the chip the opt-in kernel's, interpreted; it holds no
    # collective), whose staging carries the parts of ``apply``.
    from dgc_tpu.telemetry import trace as _tr
    step_scopes = ["dgcph.update.exchange", "dgcph.update.optimizer",
                   "dgcph.params_view", "dgcph.plumbing", "dgcph.fwd_bwd",
                   "dgcph.fwd_bwd.pack"]
    prev_tr = _tr.enable(True)
    try:
        _, step_tron, _, _ = build_fixture(
            mesh, donate=False, telemetry=False,
            compressor_kwargs={"fused_apply": True})
        tron = _step_contract(
            "trace-on-no-new-collectives", state, step_tron, inputs,
            collectives_delta=(plain, {"all-reduce": 0, "all-gather": 0}),
            require_substrings_compiled=step_scopes + [
                "dgcph.compensate", "dgcph.apply.sort", "dgcph.apply.stage"],
            no_f64=True)
        run(tron.name, tron.check)
        _, step_tron_dn, _, _ = build_fixture(mesh, compressor="none",
                                              donate=False)
        tron_dn = _step_contract(
            "trace-on-no-new-collectives[dense]", state_d, step_tron_dn,
            inputs,
            collectives_delta=(dense, {"all-reduce": 0, "all-gather": 0}),
            require_substrings_compiled=step_scopes + ["dgcph.dense"],
            no_f64=True)
        run(tron_dn.name, tron_dn.check)
        # the gradient pack's placement pass (``kernels.place_rows``, off
        # the chip interpreted) is the pack's: with the constant lowered
        # to the fixture's one-tile kernel its ops sit under part
        # ``fwd_bwd.pack``, where ``step.grad_pack_ms`` reads them
        from dgc_tpu.compression import flat as _flat
        prev_min, _flat.PLACE_MIN_BYTES = _flat.PLACE_MIN_BYTES, 4096
        try:
            state_pk, step_pk, setup_pk, _ = build_fixture(
                mesh, donate=False, head=128)
            placed = setup_pk.layout.placed_names()
            pack = _step_contract(
                "pack-pass-under-fwd_bwd.pack", state_pk, step_pk, inputs,
                require_substrings_compiled=[
                    "dgcph.fwd_bwd.pack/place_rows"],
                no_f64=True)
            run(pack.name, lambda: pack.check() + (
                [] if placed == ("Dense_0/kernel",)
                else [f"placed {placed}, expected Dense_0/kernel"]))
        finally:
            _flat.PLACE_MIN_BYTES = prev_min
    finally:
        _tr.enable(prev_tr)

    # trace off (the default): a fresh build after disable is
    # byte-identical to the plain build — phase() is Python-static, not a
    # traced no-op — and no dgcph token survives anywhere in the
    # compiled module; the same for the dense engine's build
    _, step_troff, _, _ = build_fixture(mesh, donate=False,
                                        telemetry=False)
    troff = _step_contract(
        "trace-off-compiles-away", state, step_troff, inputs,
        forbid_substrings_compiled=["dgcph."],
        identical_to=plain)
    run(troff.name, troff.check)
    _, step_troff_dn, _, _ = build_fixture(mesh, compressor="none",
                                           donate=False)
    troff_dn = _step_contract(
        "trace-off-compiles-away[dense]", state_d, step_troff_dn, inputs,
        forbid_substrings_compiled=["dgcph."],
        identical_to=dense)
    run(troff_dn.name, troff_dn.check)

    # elastic=False must cost nothing: resharding lives entirely in the
    # restore path (resilience/elastic.py is host numpy), so a step built
    # after the elastic batch-geometry resolution (an identity here — the
    # world size did not change) is byte-identical to the plain build and
    # lowers zero elastic code
    from dgc_tpu.resilience.elastic import resolve_batch_geometry
    nbps_resolved, _note = resolve_batch_geometry(8, 8, 1)
    _, step_ela, _, _ = build_fixture(mesh, donate=False, telemetry=False,
                                      num_batches_per_step=nbps_resolved)
    ela = _step_contract(
        "elastic-off-compiles-away", state, step_ela, inputs,
        forbid_substrings=["resilience/elastic"],
        identical_to=plain)
    run(ela.name, ela.check)

    _, step_don, _, _ = build_fixture(mesh, donate=True)
    don = _step_contract(
        "donated-state-aliases-outputs", state, step_don, inputs,
        donation=[0])
    run(don.name, don.check)

    # plan-matches-collectives: whatever regime mix the exchange planner
    # picks, its predicted collective counts (Plan.collectives) must
    # equal the lowered HLO's — including the all-dense plan, where the
    # sparse path must compile away to zero gathers. One candidate per
    # build forces each regime family; the engine's realized plan
    # (re-fit to the fixture's buckets) supplies the expectation, and
    # the step adds exactly one loss-mean all-reduce on top.
    from dgc_tpu.compression.planner import plan_buckets
    for reg in ("dense", "fp32", "int8", "int8_packed", "int4_packed",
                "int8_delta_idx"):
        seed_plan = plan_buckets([], fabric="32x25GbE", world=8,
                                 candidates=(reg,))
        state_p, step_p, setup_p, _ = build_fixture(
            mesh, donate=False, telemetry=False, plan=seed_plan)
        want = dict(setup_p.engine.plan.collectives(dense_reduces=1))
        want["all-reduce"] += 1     # the step's loss mean
        pmc = _step_contract(
            f"plan-matches-collectives[{reg}]", state_p, step_p, inputs,
            collectives=want, no_f64=True)
        run(pmc.name, pmc.check)

    # autotune off (ISSUE 11): a build that never names a plan or an
    # Autotuner IS the plain build, byte for byte, and no autotune code
    # lowers into the step even with the module imported — the whole
    # replanning loop is host-side Python
    import dgc_tpu.compression.autotune  # noqa: F401 — import must not leak
    _, step_atoff, _, _ = build_fixture(mesh, donate=False, telemetry=False)
    atoff = _step_contract(
        "autotune-off-compiles-away", state, step_atoff, inputs,
        forbid_substrings=["compression/autotune"],
        identical_to=plain)
    run(atoff.name, atoff.check)

    # gossip off: a build that never names a gossip plan IS the plain
    # build, byte for byte, even with the schedule module imported — the
    # decentralized exchange is a plan-time opt-in, never a runtime
    # branch
    import dgc_tpu.compression.gossip  # noqa: F401 — import must not leak
    _, step_goff, _, _ = build_fixture(mesh, donate=False, telemetry=False)
    goff = _step_contract(
        "gossip-off-compiles-away", state, step_goff, inputs,
        forbid_substrings=["compression/gossip"],
        identical_to=plain)
    run(goff.name, goff.check)

    # gossip on: the decentralized exchange keeps the SAME static
    # collective shape every round — the value + index all_gathers and
    # the dense-tail psum lower once, and the round classifier (full
    # sync vs neighborhood) only reweights what flows through them.
    # Plan.collectives() must therefore equal the lowered HLO exactly
    # as it does for every centralized regime family.
    for topo in ("ring", "hcube"):
        g_plan = plan_buckets([], fabric="32x25GbE", world=8,
                              candidates=("gossip_" + topo,))
        state_g, step_g, setup_g, _ = build_fixture(
            mesh, donate=False, telemetry=False, plan=g_plan)
        want = dict(setup_g.engine.plan.collectives(dense_reduces=1))
        want["all-reduce"] += 1     # the step's loss mean
        gon = _step_contract(
            f"gossip-on-collective-count[{topo}]", state_g, step_g,
            inputs, collectives=want, no_f64=True)
        run(gon.name, gon.check)

    # control plane (ISSUE 12): supervision, rule evaluation, and
    # remediation are host-side Python over JSONL streams — importing
    # dgc_tpu.control must leave the compiled step byte-identical to the
    # plain build and lower none of the control modules into it
    import dgc_tpu.control  # noqa: F401 — import must not leak
    _, step_ctl, _, _ = build_fixture(mesh, donate=False, telemetry=False)
    ctl = _step_contract(
        "control-plane-host-only", state, step_ctl, inputs,
        forbid_substrings=["control/supervisor", "control/plane",
                           "control/rules", "control/actions"],
        identical_to=plain)
    run(ctl.name, ctl.check)

    # cohort surgery (ISSUE 15): order files, the widened boundary
    # agreement, and the exit-76 spec arithmetic are all host-side —
    # importing the module must leave the compiled step byte-identical
    import dgc_tpu.resilience.surgery  # noqa: F401 — import must not leak
    _, step_soff, _, _ = build_fixture(mesh, donate=False, telemetry=False)
    soff = _step_contract(
        "surgery-off-compiles-away", state, step_soff, inputs,
        forbid_substrings=["resilience/surgery"],
        identical_to=plain)
    run(soff.name, soff.check)

    # an ACTIVE coordinator with a published order still adds zero
    # collectives to the step: the agreement rides the existing
    # agree_preempt host gather at the boundary, never the traced step
    def surgery_on():
        import tempfile as _tf

        from dgc_tpu.resilience import surgery as _surgery
        with _tf.TemporaryDirectory() as d:
            order = os.path.join(d, _surgery.ORDER_FILE)
            _surgery.publish_order(order, "manual", 1)
            coord = _surgery.SurgeryCoordinator(
                order, process_index=0, process_count=1)
            assert coord.agree(False).excise  # the host path is live
            _, step_son, _, _ = build_fixture(
                mesh, donate=False, telemetry=False)
            son = _step_contract(
                "surgery-on-no-new-collectives", state, step_son, inputs,
                forbid_substrings=["resilience/surgery"],
                collectives_delta=(plain, {"all-gather": 0,
                                           "all-reduce": 0}))
            return son.check()
    run("surgery-on-no-new-collectives", surgery_on)

    # online replanning: an epoch-boundary refit whose plan key() is
    # unchanged must cost ZERO recompiles (the stable autotuned-<base>
    # fabric name keeps key() fixed unless the REGIMES move) and the
    # autotuned build's collectives are exactly the plan's prediction —
    # the refit adds no exchange of its own
    def autotune_pin():
        from dgc_tpu.compression.autotune import Autotuner
        images_a, labels_a, key_a = inputs
        probe = build_fixture(mesh, donate=False, telemetry=False)[2]
        tuner = Autotuner(fabric="32x25GbE", world=8, min_points=2)
        state_a, step_a, setup_a, _ = build_fixture(
            mesh, donate=False, telemetry=False,
            plan=tuner.plan_for(probe.engine))
        out = []
        if setup_a.engine.plan.key() != tuner.plan.key():
            out.append("realized plan key differs from the tuner's plan")
        want = dict(setup_a.engine.plan.collectives(dense_reduces=1))
        want["all-reduce"] += 1     # the step's loss mean
        out += Contract(
            "autotune-replan-pins-compile", step_a,
            args=(state_a, images_a, labels_a, key_a)).expects(
            collectives=want, no_f64=True).check()
        with RecompileGuard(step_a, expect=1,
                            name="autotune-replan-pins-compile"):
            step_a(state_a, images_a, labels_a, key_a)
            # self-consistent refit: points on the fabric's own line,
            # so the replanned key cannot move
            for b in (1e4, 1e5, 1e6):
                tuner.record_step(
                    tuner.fabric.alpha_ms + b / (tuner.fabric.gbps * 1e6),
                    int(b))  # dgclint: ok[sync-in-loop] — b is a Python loop constant, not a step output
            if tuner.epoch_end(setup_a.engine) is not None:
                out.append("same-key refit signalled a rebuild")
            if tuner.refit_count != 1:
                out.append("refit did not run")
            step_a(state_a, images_a, labels_a, jax.random.PRNGKey(3))
        return out
    run("autotune-replan-pins-compile", autotune_pin)

    # two-megakernel hot path (ISSUE 16): megakernel=False must be
    # byte-identical to a build that never mentioned the flag, with
    # neither fused kernel body (_dgc_forward_kernel / _payload_apply_kernel)
    # lowered into the step — the gate is Python-static, like telemetry
    _, step_mkoff, _, _ = build_fixture(
        mesh, donate=False, telemetry=False,
        compressor_kwargs={"megakernel": False})
    mkoff = _step_contract(
        "megakernel-off-compiles-away", state, step_mkoff, inputs,
        forbid_substrings=["_dgc_forward_kernel", "_payload_apply_kernel"],
        identical_to=plain)
    run(mkoff.name, mkoff.check)

    # megakernel on: the fused forward/apply passes restructure
    # per-bucket COMPUTE only — the wire protocol (payload lanes,
    # transmit record) is untouched, so the collective count is exactly
    # the plain build's (zero all-gather / all-reduce delta)
    state_mk, step_mkon, _, _ = build_fixture(
        mesh, donate=False, telemetry=False,
        compressor_kwargs={"megakernel": True})
    mkon = _step_contract(
        "megakernel-on-no-new-collectives", state_mk, step_mkon, inputs,
        collectives_delta=(plain, {"all-gather": 0, "all-reduce": 0}),
        no_f64=True)
    run(mkon.name, mkon.check)

    run("fused-epilogue-no-opt-barriers",
        lambda: _epilogue_contract().check())

    def recompile():
        images, labels, key = inputs
        with RecompileGuard(step_plain, expect=1,
                            name="flat-step-same-shapes"):
            step_plain(state, images, labels, key)
            step_plain(state, images, labels, jax.random.PRNGKey(2))
        return []
    run("recompile-guard-same-shapes", recompile)

    run("shard-state-collective-free",
        lambda: shard_state_source_check(root))
    return results


def _epilogue_contract() -> Contract:
    """PR 1's fused payload-apply epilogue must lower barrier-free: an
    optimization_barrier between decompress and apply would pin the
    intermediate accumulator and defeat the single-pass fusion (see the
    note on kernels.opaque_view)."""
    import jax
    import jax.numpy as jnp

    from dgc_tpu.ops import kernels

    total = 4096
    values = jnp.ones((256,), jnp.float32)
    indices = jnp.arange(256, dtype=jnp.int32)
    flags = jnp.ones((256,), jnp.bool_)
    fn = jax.jit(lambda v, i, f: kernels.payload_apply_bits(v, i, f, total))
    return Contract("fused-epilogue-no-opt-barriers", fn,
                    args=(values, indices, flags)).expects(
        forbid_ops=["optimization-barrier"], no_f64=True)
