"""Tests for cohort surgery (ISSUE 15; docs/RESILIENCE.md §"Cohort
surgery"): the fault-plan hang/exit tokens, the order / exit-record file
protocol, the widened (preempt, verdict, target) agreement lane with its
hang-safe deadline tier, the supervisor's exit-76 surgery handling and
heartbeat hang escalation, the device-pool ledger, the excise/readmit
detectors and actions, the monitor's COHORT surface — and the 3-process
drill: ``DGC_FAULTS=hang@5-5`` on worker 2, supervisor SIGKILLs the hung
process, survivors exit 76 with an atomic emergency checkpoint and
relaunch as W=2 under the published shrunk spec, worker 2 passes the
re-init probe, the device pool frees its slot, and a rule-driven readmit
grows the cohort back to W=3 — every transition an audited
``control_action``.

Everything here is host-only (subprocesses + files + threads, no jax),
so the whole file is ``fast``-marked (scripts/t1.sh SURGERY_SMOKE).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from dgc_tpu.control import actions, rules
from dgc_tpu.control.plane import ControlPlane, DevicePool, RunSpec
from dgc_tpu.control.rules import Rule
from dgc_tpu.control.supervisor import Supervisor, parse_env_file
from dgc_tpu.resilience import faults, surgery
from dgc_tpu.telemetry import monitor, registry

from surgery_worker import _read_step
from test_fleet import _write_run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "tests", "surgery_worker.py")


# --------------------------------------------------------------------- #
# fault plan: hang / exit tokens                                         #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_fault_plan_hang_exit_tokens(monkeypatch):
    p = faults.plan("hang@5")
    assert p.hang_window == (5, None) and p.hang_secs is None
    p = faults.plan("hang:secs=2@5-8")
    assert p.hang_window == (5, 8) and p.hang_secs == 2
    p = faults.plan("hang@5-5")
    assert p.hang_window == (5, 5)
    p = faults.plan("exit:code=76@7")
    assert p.exit_code == 76 and p.exit_window == (7, None)
    p = faults.plan("exit@3")
    assert p.exit_code == 1 and p.exit_window == (3, None)
    # composes with the existing grammar
    p = faults.plan("slow:ms=40@2-9,hang:secs=1@5-5,exit:code=9@20")
    assert p.slow_ms == 40 and p.slow_window == (2, 9)
    assert p.hang_window == (5, 5) and p.exit_code == 9
    with pytest.raises(ValueError):
        faults.plan("hangg@5")

    # unset -> byte-identical plan: every hook is an identity
    monkeypatch.delenv(faults.ENV, raising=False)
    assert faults.plan() == faults.FaultPlan()

    # windowed hang only fires inside the window (and never without a
    # step); a bounded stall returns
    monkeypatch.setenv(faults.ENV, "hang:secs=0@5-5")
    t0 = time.time()
    faults.maybe_hang(None)
    faults.maybe_hang(4)
    faults.maybe_hang(6)
    faults.maybe_hang(5)        # secs=0: stalls zero seconds, returns
    assert time.time() - t0 < 1.0
    monkeypatch.setenv(faults.ENV, "exit:code=42@7")
    faults.maybe_exit(6)        # out of window: no exit
    # the exit itself, in a subprocess (os._exit bypasses everything)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]);"
         "from dgc_tpu.resilience import faults; faults.maybe_exit(7)",
         ROOT],
        env=dict(os.environ, DGC_FAULTS="exit:code=42@7"), timeout=60)
    assert proc.returncode == 42


# --------------------------------------------------------------------- #
# order / exit-record files                                              #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_order_file_protocol(tmp_path):
    path = str(tmp_path / surgery.ORDER_FILE)
    assert surgery.read_order(path) is None          # absent
    surgery.publish_order(path, "desync", 2, step=30,
                          extra={"rule_fired": 3})
    rec = surgery.read_order(path)
    assert rec["verdict"] == "desync" and rec["target"] == 2
    assert rec["step"] == 30 and rec["rule_fired"] == 3 and rec["t"] > 0

    with pytest.raises(ValueError):
        surgery.publish_order(path, "none", 1)
    with pytest.raises(ValueError):
        surgery.publish_order(path, "bogus", 1)

    # torn / malformed degrade to "no order", never crash a step
    with open(path, "w") as f:
        f.write('{"verdict": "des')
    assert surgery.read_order(path) is None
    with open(path, "w") as f:
        json.dump({"verdict": "desync"}, f)          # no target
    assert surgery.read_order(path) is None
    with open(path, "w") as f:
        json.dump(["not", "a", "dict"], f)
    assert surgery.read_order(path) is None

    surgery.clear_order(path)
    surgery.clear_order(path)                        # idempotent
    assert surgery.read_order(path) is None
    # atomic writes leave no temp litter
    assert not [p for p in os.listdir(tmp_path) if p.startswith(".surgery")]


@pytest.mark.fast
def test_exit_record_roundtrip(tmp_path):
    path = str(tmp_path / surgery.EXIT_RECORD)
    assert surgery.read_exit_record(path) is None
    ag = surgery.Agreement(excise=True, target=1, verdict="hang", lost=True)
    surgery.write_exit_record(path, ag, world=3, process_index=0, step=17)
    rec = surgery.read_exit_record(path)
    assert rec["verdict"] == "hang" and rec["target"] == 1
    assert rec["lost"] is True and rec["world"] == 3
    assert rec["process_index"] == 0 and rec["step"] == 17


# --------------------------------------------------------------------- #
# the agreement lane                                                     #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_lanes_encode_decode():
    row = surgery.encode_lanes(False, None)
    assert row.tolist() == [0.0, 0.0, 0.0] and row.dtype == np.float32
    row = surgery.encode_lanes(True, {"verdict": "desync", "target": 0})
    assert row.tolist() == [1.0, 1.0, 1.0]           # target+1 offset

    none = surgery.encode_lanes(False, None)
    ag = surgery.decode_lanes(np.stack([none, none, none]))
    assert ag == surgery.Agreement()                 # quiet boundary

    ag = surgery.decode_lanes(np.stack([
        surgery.encode_lanes(True, None),            # one saw SIGTERM
        none,
        surgery.encode_lanes(False, {"verdict": "desync", "target": 2}),
    ]))
    assert ag.preempt and ag.excise and ag.target == 2
    assert ag.verdict == "desync" and not ag.lost

    # disagreement: the highest verdict code wins deterministically
    ag = surgery.decode_lanes(np.stack([
        surgery.encode_lanes(False, {"verdict": "desync", "target": 1}),
        surgery.encode_lanes(False, {"verdict": "hang", "target": 2}),
    ]))
    assert ag.verdict == "hang" and ag.target == 2

    # a verdict with no target is not an excise
    ag = surgery.decode_lanes(np.asarray([[0.0, 4.0, 0.0]], np.float32))
    assert not ag.excise and ag.target == -1 and ag.verdict == "none"


@pytest.mark.fast
def test_coordinator_agreement_paths(tmp_path):
    order_path = str(tmp_path / surgery.ORDER_FILE)

    def cohort_gather(payload):
        # two quiet peers ride along
        quiet = surgery.encode_lanes(False, None)
        return np.stack([payload, quiet, quiet])

    coord = surgery.SurgeryCoordinator(
        order_path, boundary_timeout=5.0, retries=1, backoff=0.05,
        process_index=0, process_count=3, allgather=cohort_gather,
        log=lambda m: None)
    assert coord.agree(False) == surgery.Agreement()
    surgery.publish_order(order_path, "straggler", 1)
    ag = coord.agree(True)
    assert ag.preempt and ag.excise and ag.target == 1
    assert ag.verdict == "straggler"
    assert not coord.excised(ag)
    assert coord.excised(surgery.Agreement(excise=True, target=0))

    # hang tier: the gather never completes -> bounded budget -> lost
    stuck = surgery.SurgeryCoordinator(
        order_path, boundary_timeout=0.05, retries=2, backoff=0.05,
        process_index=0, process_count=3,
        allgather=lambda p: time.sleep(30), log=lambda m: None)
    t0 = time.time()
    ag = stuck.agree(False)
    assert ag.lost and ag.verdict == "hang" and not ag.excise
    assert time.time() - t0 < 5.0                    # bounded, not 30s

    # a SIGKILLed peer surfaces as a collective error -> same lost path
    def boom(payload):
        raise RuntimeError("connection reset by peer")
    dead = surgery.SurgeryCoordinator(
        order_path, boundary_timeout=1.0, retries=0, backoff=0.05,
        process_index=0, process_count=3, allgather=boom,
        log=lambda m: None)
    assert dead.agree(False).lost

    # late arrival INSIDE the backoff budget: the same in-flight gather
    # completes, no agreement is lost
    def late(payload):
        time.sleep(0.3)
        return cohort_gather(payload)
    slowpoke = surgery.SurgeryCoordinator(
        order_path, boundary_timeout=0.1, retries=3, backoff=0.15,
        process_index=0, process_count=3, allgather=late,
        log=lambda m: None)
    ag = slowpoke.agree(False)
    assert not ag.lost and ag.excise and ag.target == 1

    # single-process short circuit: the order is honored with NO
    # communication at all
    def forbidden(payload):
        raise AssertionError("single-process agree must not communicate")
    solo = surgery.SurgeryCoordinator(
        order_path, process_index=0, process_count=1, allgather=forbidden)
    ag = solo.agree(False)
    assert ag.excise and ag.target == 1 and ag.verdict == "straggler"
    surgery.clear_order(order_path)
    assert solo.agree(True) == surgery.Agreement(preempt=True)


@pytest.mark.fast
def test_shrink_and_remap():
    assert surgery.shrink_updates(3, 2) == {"JAX_NUM_PROCESSES": "2"}
    assert surgery.shrink_updates(2, 0) == {"JAX_NUM_PROCESSES": "1"}
    assert surgery.shrink_updates(1, 0) is None      # nothing to shrink to
    assert surgery.shrink_updates(4, -1) is None     # unknown target
    assert surgery.shrink_updates(4, 4) is None      # out of range

    assert surgery.remap_process_id(2, 2) is None    # self-excision
    assert surgery.remap_process_id(3, 2) == 2       # above the hole
    assert surgery.remap_process_id(1, 2) == 1       # below: unchanged


@pytest.mark.fast
def test_probe_checksum_deterministic():
    a = np.arange(64, dtype=np.float32)
    b = np.ones((4, 4), np.int32)
    assert surgery.probe_checksum([a, b]) == surgery.probe_checksum(
        [a.copy(), b.copy()])
    assert surgery.probe_checksum([a]) != surgery.probe_checksum([a + 1])
    # shape/dtype are part of the identity, not just the bytes
    assert surgery.probe_checksum([a]) != surgery.probe_checksum(
        [a.reshape(8, 8)])


# --------------------------------------------------------------------- #
# supervisor: exit 76, hang escalation                                   #
# --------------------------------------------------------------------- #

_SURGERY_CHILD = """\
import json, os, sys
sys.path.insert(0, sys.argv[2])
run = sys.argv[1]
ck = os.path.join(run, "checkpoints"); os.makedirs(ck, exist_ok=True)
marker = os.path.join(run, "ran")
if os.path.exists(marker):
    sys.exit(0)
open(marker, "w").write("1")
with open(os.path.join(ck, "latest.json"), "w") as f:
    json.dump({"epoch": 1}, f)
from dgc_tpu.resilience import surgery
surgery.write_exit_record(
    os.path.join(ck, surgery.EXIT_RECORD),
    surgery.Agreement(excise=True, target=int(os.environ["TGT"]),
                      verdict="hang", lost=True),
    world=3, process_index=int(os.environ["JAX_PROCESS_ID"]), step=5)
sys.exit(76)
"""


def _surgery_sup(tmp_path, pid, target):
    run = tmp_path / "run"
    run.mkdir(exist_ok=True)
    script = tmp_path / "child.py"
    script.write_text(_SURGERY_CHILD)
    envf = tmp_path / "cohort.env"
    envf.write_text("JAX_NUM_PROCESSES=3\n")
    return Supervisor(
        [sys.executable, str(script), str(run), ROOT],
        retries=0, backoff=0.05, env_file=str(envf),
        watch=str(run / "checkpoints"),
        events=str(tmp_path / "ev.jsonl"),
        extra_env={"JAX_PROCESS_ID": str(pid), "TGT": str(target)})


@pytest.mark.fast
def test_supervisor_exit_76_survivor_relaunch(tmp_path):
    # survivor (pid 1, target 2): apply record, publish shrunk spec,
    # relaunch immediately with the failure budget reset (retries=0!)
    sup = _surgery_sup(tmp_path, pid=1, target=2)
    rc = sup.run(install_signals=False)
    assert rc == 0 and sup.launches == 2 and sup.state == "done"
    assert sup.quarantined is None
    assert parse_env_file(str(tmp_path / "cohort.env")) == {
        "JAX_NUM_PROCESSES": "2"}
    assert sup.extra_env["JAX_PROCESS_ID"] == "1"    # below the hole
    evs = [json.loads(l) for l in (tmp_path / "ev.jsonl").read_text()
           .splitlines()]
    assert [e["event"] for e in evs] == ["launch", "surgery", "launch",
                                         "done"]
    s = evs[1]
    assert s["rc"] == 76 and s["verdict"] == "hang" and s["target"] == 2
    assert s["lost"] is True and s["world"] == 2
    assert s["published"] == {"JAX_NUM_PROCESSES": "2"}
    # the relaunch ran under the published spec
    assert evs[2]["cohort"]["JAX_NUM_PROCESSES"] == "2"

    # the record is applied exactly once per publish
    assert sup._apply_surgery(76) == {}


@pytest.mark.fast
def test_supervisor_exit_76_self_excision_quarantines(tmp_path):
    # pid 2 IS the target: the shrunk spec has no seat -> quarantined
    # for the readmit probe, NOT relaunched into a dead slot
    sup = _surgery_sup(tmp_path, pid=2, target=2)
    rc = sup.run(install_signals=False)
    assert rc == 76 and sup.launches == 1
    assert sup.state == "quarantined"
    assert sup.quarantined == "excised:hang"
    evs = [json.loads(l) for l in (tmp_path / "ev.jsonl").read_text()
           .splitlines()]
    assert [e["event"] for e in evs] == ["launch", "quarantined"]
    assert evs[1]["reason"] == "excised:hang"


@pytest.mark.fast
def test_supervisor_hang_escalation_sigkills_stale_heartbeat(tmp_path):
    hb = tmp_path / "heartbeat"
    sup = Supervisor(
        [sys.executable, "-c", "import time; time.sleep(60)"],
        retries=0, backoff=0.05, events=str(tmp_path / "ev.jsonl"),
        hang_timeout=0.6, heartbeat=str(hb))
    t0 = time.time()
    rc = sup.run(install_signals=False)
    assert time.time() - t0 < 30.0                   # not the 60s sleep
    assert rc != 0 and sup.state == "quarantined"
    assert sup.quarantined.startswith("hang:no heartbeat")
    evs = [json.loads(l) for l in (tmp_path / "ev.jsonl").read_text()
           .splitlines()]
    assert [e["event"] for e in evs] == ["launch", "hang_kill",
                                         "quarantined"]
    assert evs[2]["reason"].startswith("hang:")

    # a child that beats the heartbeat is never escalated
    beat = ("import os, time\n"
            "for _ in range(20):\n"
            "    open(os.environ['DGC_HEARTBEAT'], 'a').close()\n"
            "    os.utime(os.environ['DGC_HEARTBEAT'])\n"
            "    time.sleep(0.05)\n")
    sup2 = Supervisor([sys.executable, "-c", beat], retries=0,
                      backoff=0.05, events=str(tmp_path / "ev2.jsonl"),
                      hang_timeout=0.6, heartbeat=str(tmp_path / "hb2"))
    assert sup2.run(install_signals=False) == 0
    assert sup2.state == "done" and sup2.quarantined is None


# --------------------------------------------------------------------- #
# device-pool ledger                                                     #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_device_pool_one_way_idempotent():
    pool = DevicePool({"a": 4, "b": 2, "c": 1})
    assert pool.free == 0
    assert pool.snapshot()["total"] == 7 and pool.snapshot()["active"] == 7

    pool.quarantine("b")
    pool.quarantine("b")                             # idempotent
    assert pool.snapshot()["quarantined"] == ["b"]
    assert pool.free == 0                            # held, not free

    pool.release("a")                                # active: not releasable
    assert pool.free == 0
    pool.release("b")                                # quarantined -> freed
    pool.release("b")
    assert pool.free == 2
    snap = pool.snapshot()
    assert snap["freed"] == ["b"] and snap["active"] == 5

    pool.quarantine("b")                             # freed: one-way, no-op
    assert pool.free == 2
    pool.activate("b")                               # readmit
    assert pool.free == 0 and pool.snapshot()["active"] == 7
    pool.activate("nope")                            # unknown run ignored
    assert pool.snapshot()["total"] == 7


# --------------------------------------------------------------------- #
# detectors + actions + registry                                         #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_surgery_detectors_on_synthetic_snapshots():
    assert rules.detect_excise({}) is None
    assert rules.detect_excise({"last_supervise": {
        "event": "quarantined", "reason": "exit:70"}}) is None
    ev = rules.detect_excise({"last_supervise": {
        "event": "hang_kill", "reason": "no heartbeat for 2.1s",
        "cohort": {"JAX_PROCESS_ID": "2", "JAX_NUM_PROCESSES": "3"}}})
    assert ev["kind"] == "hang" and ev["worker"] == 2 and ev["world"] == 3
    # the FROM-world comes from the event's launch-time cohort stamp,
    # NOT the live (already-shrunk) spec
    ev = rules.detect_excise({
        "last_supervise": {"event": "quarantined", "reason": "hang:stale",
                           "cohort": {"JAX_PROCESS_ID": "1",
                                      "JAX_NUM_PROCESSES": "3"}},
        "cohort": {"spec_world": 2}})
    assert ev["world"] == 3
    ev = rules.detect_excise({"last_supervise": {
        "event": "hang_kill", "reason": "x", "cohort": {}},
        "cohort": {"spec_world": 4}})
    assert ev["world"] == 4                          # fallback

    assert rules.detect_readmit({}) is None
    assert rules.detect_readmit({"cohort": {
        "probe": {"passed": True}, "pool_free": 0}}) is None
    assert rules.detect_readmit({"cohort": {
        "probe": {"passed": False, "rc": 1}, "pool_free": 2}}) is None
    ev = rules.detect_readmit({"cohort": {
        "probe": {"passed": True, "rc": 0, "checksum": "abc"},
        "pool_free": 2, "spec_world": 2}})
    assert ev == {"kind": "readmit", "pool_free": 2, "probe_rc": 0,
                  "checksum": "abc", "target_world": 3}


@pytest.mark.fast
def test_act_excise_and_readmit(tmp_path):
    watch = tmp_path / "checkpoints"
    watch.mkdir()
    envf = tmp_path / "cohort.env"
    envf.write_text("JAX_NUM_PROCESSES=3\n")
    sup = Supervisor([sys.executable, "-c", "pass"], env_file=str(envf),
                     watch=str(watch))

    # a non-hang excise publishes order + spec but quarantines nothing
    # (the workers take the orderly exit-76 path themselves)
    res = actions.act_excise(
        sup, {"kind": "desync", "worker": 1, "world": 3, "hits": 2},
        env_updates={"JAX_NUM_PROCESSES": "2"})
    order = surgery.read_order(str(watch / surgery.ORDER_FILE))
    assert order["verdict"] == "desync" and order["target"] == 1
    assert order["rule_fired"] == 2
    assert res["published"] == {"JAX_NUM_PROCESSES": "2"}
    assert res["order"]["target"] == 1
    assert sup.quarantined is None

    # a hang excise also quarantines (the corpse is already SIGKILLed)
    res = actions.act_excise(sup, {"kind": "hang", "worker": 2},
                             env_updates={})
    assert sup.quarantined == "excised:hang"
    assert res["quarantined"] == "excised:hang" and res["already"] is False

    # an unknown verdict kind degrades to "manual", never raises
    sup2 = Supervisor([sys.executable, "-c", "pass"], watch=str(watch))
    actions.act_excise(sup2, {"kind": "weird", "worker": 0})
    assert surgery.read_order(
        str(watch / surgery.ORDER_FILE))["verdict"] == "manual"

    # readmit: stale order + exit record cleared, grown spec published,
    # plane-provided relaunch + cohort restart executed and audited
    surgery.write_exit_record(
        str(watch / surgery.EXIT_RECORD),
        surgery.Agreement(excise=True, target=2, verdict="hang"),
        world=3, process_index=0)
    res = actions.act_readmit(
        sup2, {"kind": "readmit", "target_world": 3},
        env_updates={"JAX_NUM_PROCESSES": "3"},
        relauncher=lambda: True, cohort_restart=lambda: ["w0", "w1"])
    assert not os.path.exists(watch / surgery.ORDER_FILE)
    assert not os.path.exists(watch / surgery.EXIT_RECORD)
    assert res["relaunched"] is True
    assert res["cohort_restarted"] == ["w0", "w1"]
    assert parse_env_file(str(envf)) == {"JAX_NUM_PROCESSES": "2"}

    # registry: both are first-class audited control actions
    assert "excise" in registry.control_action_names()
    assert "readmit" in registry.control_action_names()
    assert "excise" in actions.ACTIONS and "readmit" in actions.ACTIONS
    registry.validate_control_action({
        "event": "control_action", "run": "w2", "run_id": "w2-x",
        "rule": "hang-excise", "action": "excise",
        "evidence": {"kind": "hang", "worker": 2}, "result": res,
        "t": time.time()})


@pytest.mark.fast
def test_monitor_cohort_line_and_gauges(tmp_path):
    run = str(tmp_path / "run")
    _write_run(run, hosts=1, world=4, steps=6)
    with open(os.path.join(run, "cohort.json"), "w") as f:
        json.dump({"total": 3, "active": 2, "pool_free": 1,
                   "quarantined": ["w2"], "freed": ["w2"],
                   "spec_world": 3, "t": time.time(),
                   "probe": {"passed": True, "rc": 0}}, f)
    snap = monitor.collect(run)
    assert snap["cohort"]["spec_world"] == 3

    status = monitor.render_status(snap)
    assert "COHORT:" in status
    assert "world 2/3" in status
    assert "quarantined=[w2]" in status
    assert "pool free 1" in status and "probe passed" in status

    om = monitor.render_openmetrics(snap)
    size_lines = [l for l in om.splitlines()
                  if l.startswith("dgc_cohort_size{")]
    assert size_lines and size_lines[0].endswith(" 3")
    assert "dgc_pool_free{" in om

    # a torn cohort.json degrades to "no COHORT surface", not an error
    with open(os.path.join(run, "cohort.json"), "w") as f:
        f.write('{"total": 3, "act')
    snap = monitor.collect(run)
    assert "cohort" not in snap
    assert "COHORT:" not in monitor.render_status(snap)
    assert "dgc_cohort_size" not in monitor.render_openmetrics(snap)


# --------------------------------------------------------------------- #
# the 3-process excise/readmit drill                                     #
# --------------------------------------------------------------------- #

#: the drill's worker 2 hangs at this step; its readmit waits until the
#: survivors are this far past it
_HANG_STEP, _READMIT_AFTER = 5, 20


def _surgery_rules(cohort_dir):
    # the shipped detectors and action mapping, tuned tick-fast. Readmit
    # holds back until the survivors HAVE run a stretch at W=2 — by the
    # cohort's own progress, not by the clock: a readmitted life resumes
    # at the cohort's step, and one that resumed inside worker 2's fault
    # window would hang again with the readmit budget spent. (A hold-back
    # in ticks raced the survivors' exit-76 path, which takes the barrier
    # timeout PLUS a jax import: surgery.write_exit_record publishes
    # through dgc_tpu.serving.protocol, and dgc_tpu/serving/__init__.py
    # imports the exporter.)
    def readmit_after_stretch(snap):
        step = _read_step(os.path.join(cohort_dir, "progress.json"))
        return rules.detect_readmit(snap) if step >= _READMIT_AFTER else None

    return (
        Rule("hang-excise", rules.detect_excise, "excise",
             min_hits=1, debounce_s=60.0, budget=1),
        Rule("probe-readmit", readmit_after_stretch, "readmit",
             min_hits=2, debounce_s=60.0, budget=1),
    )


@pytest.mark.fast
def test_cohort_surgery_drill(tmp_path):
    root = str(tmp_path)
    cohort_dir = os.path.join(root, "cohort")
    env_file = os.path.join(root, "cohort.env")
    with open(env_file, "w") as f:
        f.write("JAX_NUM_PROCESSES=3\n")

    def spec(i, **kw):
        run_dir = os.path.join(root, f"w{i}")
        env = {"JAX_PROCESS_ID": str(i), "DGC_BOUNDARY_TIMEOUT": "6"}
        env.update(kw.pop("env", {}))
        return RunSpec(
            f"w{i}",
            [sys.executable, WORKER, run_dir, "--cohort", cohort_dir,
             "--steps", "140", "--step-ms", "30"],
            run_dir=run_dir, env_file=env_file, env=env, backoff=0.1,
            **kw)

    specs = [
        spec(0), spec(1),
        # worker 2 hangs at step 5 (exactly once: the readmitted life
        # resumes past the window); its supervisor escalates via the
        # stale heartbeat, and its probe re-earns the slot. The budget
        # counts from launch, so it also has to cover a worker's start
        # on a host that runs the suite's other xdist workers (the
        # barrier timeout stays above it: the hang is escalated before
        # the survivors give the member up)
        spec(2, env={"DGC_FAULTS": f"hang@{_HANG_STEP}-{_HANG_STEP}"},
             hang_timeout=3.0,
             probe_cmd=[sys.executable, WORKER,
                        os.path.join(root, "w2"), "--cohort", cohort_dir,
                        "--probe"]),
    ]
    plane = ControlPlane(specs, root, rules=_surgery_rules(cohort_dir),
                         interval=0.25)
    final = plane.run(max_ticks=400)

    # every run completed: the cohort went 3 -> 2 -> 3 and finished
    for name in ("w0", "w1", "w2"):
        assert final[name]["rc"] == 0, (name, final[name])
        assert final[name]["state"] == "done"
    # w2's first life was SIGKILLed + quarantined; its readmitted life
    # runs under a FRESH supervisor (one launch)
    assert final["w2"]["launches"] == 1
    # survivors: initial launch + exit-76 surgery relaunch + readmit
    # cohort restart
    assert final["w0"]["launches"] >= 3
    assert final["w1"]["launches"] >= 3

    # exactly two audited remediations, both on w2, in surgery order
    assert [(a["run"], a["action"]) for a in plane.actions] == \
        [("w2", "excise"), ("w2", "readmit")]
    exc, adm = plane.actions
    assert exc["evidence"]["kind"] == "hang"
    assert exc["evidence"]["worker"] == 2
    assert exc["evidence"]["world"] == 3             # FROM-world
    assert exc["result"]["published"] == {"JAX_NUM_PROCESSES": "2"}
    # the hang escalation quarantined the run BEFORE the audit: the
    # action records that it was already held, with the hang reason
    assert exc["result"]["already"] is True
    assert exc["result"]["quarantined"].startswith("hang:")
    assert adm["evidence"]["kind"] == "readmit"
    assert adm["evidence"]["pool_free"] == 1
    assert adm["evidence"]["target_world"] == 3
    assert "checksum" in adm["evidence"]             # the probe's output
    assert adm["result"]["published"] == {"JAX_NUM_PROCESSES": "3"}
    assert adm["result"]["relaunched"] is True
    assert set(adm["result"]["cohort_restarted"]) == {"w0", "w1"}

    # the grown spec is what the fleet ends on
    assert parse_env_file(env_file) == {"JAX_NUM_PROCESSES": "3"}

    # survivors took the exit-76 path with an atomic emergency
    # checkpoint and an exit record naming the hung member
    for name in ("w0", "w1"):
        rec = surgery.read_exit_record(
            os.path.join(root, name, "checkpoints", surgery.EXIT_RECORD))
        assert rec is not None, name
        assert rec["target"] == 2 and rec["world"] == 3
        assert rec["verdict"] == "hang" and rec["lost"] is True
        evs = [json.loads(l) for l in open(
            os.path.join(root, name, "supervise_events.jsonl"))]
        surgeries = [e for e in evs if e["event"] == "surgery"]
        assert len(surgeries) == 1 and surgeries[0]["rc"] == 76
        assert surgeries[0]["world"] == 2
        # launch cohort specs walked 3 -> 2 -> 3
        worlds = [e["cohort"].get("JAX_NUM_PROCESSES") for e in evs
                  if e["event"] == "launch"]
        assert worlds[0] == "3" and "2" in worlds and worlds[-1] == "3"

    # the hung worker: hang_kill then quarantined with the hang reason
    evs = [json.loads(l) for l in open(
        os.path.join(root, "w2", "supervise_events.jsonl"))]
    kinds = [e["event"] for e in evs]
    assert "hang_kill" in kinds
    q = next(e for e in evs if e["event"] == "quarantined")
    assert q["reason"].startswith("hang:")
    # ... and its readmit clears the stale exit record
    assert surgery.read_exit_record(os.path.join(
        root, "w2", "checkpoints", surgery.EXIT_RECORD)) is None

    # every member finished all 140 steps; progress is cohort-wide
    for name in ("w0", "w1", "w2"):
        with open(os.path.join(root, name, "checkpoints",
                               "latest.json")) as f:
            assert json.load(f)["epoch"] == 140, name
    with open(os.path.join(cohort_dir, "progress.json")) as f:
        assert json.load(f)["step"] == 140

    # the fleet event stream is the audit trail: probe + every action
    events = [json.loads(l) for l in open(
        os.path.join(root, "control_events.jsonl"))]
    probes = [e for e in events if e["event"] == "probe"]
    assert probes and probes[0]["run"] == "w2"
    assert probes[0]["passed"] is True and "checksum" in probes[0]
    action_evs = [e for e in events if e["event"] == "control_action"]
    assert len(action_evs) == 2
    for e in action_evs:
        registry.validate_control_action(e)

    # the ledger surface: cohort.json per run + fleet root, COHORT line
    # and gauges on the monitor
    with open(os.path.join(root, "cohort.json")) as f:
        fleet_cohort = json.load(f)
    assert fleet_cohort["total"] == 3 and fleet_cohort["free"] == 0
    assert fleet_cohort["runs"]["w2"] == "active"    # readmitted
    snap = monitor.collect(os.path.join(root, "w2"))
    assert snap["cohort"]["spec_world"] == 3
    assert "COHORT:" in monitor.render_status(snap)
    om = monitor.render_openmetrics(snap)
    assert "dgc_cohort_size" in om and "dgc_pool_free" in om
    # the readmitted worker's final life recorded the grown world
    assert snap["static"]["num_processes"] == 3
