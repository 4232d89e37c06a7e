"""Tests for the fleet observability layer (ISSUE 10): registry fleet
schema, the in-graph packed gather, tolerant shard readers + multi-host
merge, the straggler table, the rolling-band desync detector, the live
monitor's OpenMetrics/status renderers + HTTP endpoint, the supervisor's
event stamping, and the ``slow`` fault token.

All host-side pieces run against synthetic JSONL runs — no training, so
the whole file is ``fast``-marked (scripts/t1.sh MONITOR_SMOKE). The
in-graph gather runs once on the 8-fake-device mesh; the cross-process
drill lives in tests/test_multiprocess.py.
"""

import importlib.util
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from dgc_tpu.telemetry import fleet, monitor, registry
from dgc_tpu.telemetry import sink as sink_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# synthetic runs                                                         #
# --------------------------------------------------------------------- #

def _write_run(root, hosts=2, world=4, steps=40, straggler=None,
               torn=False, rotate=False):
    """A fleet-shaped run dir: ``<root>/telemetry/host<i>/*.jsonl`` with
    replicated per-worker columns, an event row on host0, optionally a
    torn tail on the last host and a rotated shard on host0."""
    header = registry.make_header(
        {"world": world, "num_params": 1000, "payload_elems": 50},
        fleet=True)
    rng = np.random.RandomState(0)
    for h in range(hosts):
        hd = os.path.join(root, "telemetry", f"host{h}")
        os.makedirs(hd, exist_ok=True)
        lines = [json.dumps(header)]
        if h == 0:
            lines.append(json.dumps(
                {"event": "engine_rebuild", "epoch": 0, "t_host": 99.0}))
        recs = []
        for i in range(steps):
            clock = 10.0 + rng.rand(world)
            if straggler is not None:
                clock[straggler] += 80.0
            mass = 100.0 * (1.0 + 0.02 * rng.randn(world))
            recs.append({
                "step": i, "t_host": 100.0 + 0.5 * i,
                "loss": 2.0 - 0.01 * i,
                "grad_norm": 1.0, "payload_elems": 50.0,
                "w_clock": [round(float(x), 3) for x in clock],
                "w_grad_norm": [1.0] * world,
                "w_residual_mass": [round(float(x), 4) for x in mass],
                "w_sent_ratio": [0.05] * world,
                "straggler": float(int(np.argmax(clock))),
                "straggler_gap": round(float(clock.max() - clock.min()), 3),
                "worker_skew": 0.1,
            })
        if rotate and h == 0:
            cut = steps // 2
            open(os.path.join(hd, "telemetry.jsonl"), "w").write(
                "\n".join(lines + [json.dumps(r) for r in recs[:cut]])
                + "\n")
            open(os.path.join(hd, "telemetry.1.jsonl"), "w").write(
                "\n".join([json.dumps(header)]
                          + [json.dumps(r) for r in recs[cut:]]) + "\n")
            continue
        text = "\n".join(lines + [json.dumps(r) for r in recs]) + "\n"
        if torn and h == hosts - 1:
            text += '{"step": 999, "w_clock": [1'     # live-writer tear
        open(os.path.join(hd, "telemetry.jsonl"), "w").write(text)
    return root


# --------------------------------------------------------------------- #
# registry: fleet schema                                                 #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_registry_fleet_schema():
    names = registry.fleet_stat_names()
    assert len(names) == len(set(names))
    kinds = {s.name: s.kind for s in registry.FLEET_METRICS}
    for lane in ("w_clock", "w_grad_norm", "w_residual_mass",
                 "w_sent_ratio"):
        assert kinds[lane] == "per_worker"
    for scalar in ("straggler", "straggler_gap", "worker_skew"):
        assert kinds[scalar] == "scalar"
    # the gate-able dispersion metrics are registered lower-is-better
    by_name = registry.spec_by_name()
    assert by_name["worker_skew"].better == "lower"
    assert by_name["straggler_gap"].better == "lower"
    run_names = {s.name for s in registry.RUN_METRICS}
    assert {"worker_skew", "straggler_gap"} <= run_names

    h = registry.make_header({"world": 8}, fleet=True)
    assert {m["name"] for m in h["fleet_metrics"]} == set(names)
    assert "fleet_metrics" not in registry.make_header({})
    # additive keys: no version bump
    assert h["version"] == registry.SCHEMA_VERSION

    good = {n: 0.0 for n in names}
    registry.validate_fleet_stats(good)
    with pytest.raises(ValueError, match="missing"):
        registry.validate_fleet_stats(
            {k: v for k, v in good.items() if k != "w_clock"})
    assert set(registry.fleet_out_specs(lambda: "P()")) == set(names)


# --------------------------------------------------------------------- #
# tolerant reader                                                        #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_read_run_tolerant_truncated_shard(tmp_path):
    path = tmp_path / "telemetry.jsonl"
    header = registry.make_header({"world": 2}, fleet=True)
    lines = [json.dumps(header)] + [
        json.dumps({"step": i, "grad_norm": 1.0}) for i in range(3)]
    path.write_text("\n".join(lines) + "\n"
                    + '{"step": 3, "grad_norm": 0.')  # torn mid-write
    h, recs, skipped = sink_mod.read_run_tolerant(str(path))
    assert h["schema"] == registry.SCHEMA
    assert [r["step"] for r in recs] == [0, 1, 2]
    assert skipped == 1
    # the strict reader refuses the same file
    with pytest.raises(json.JSONDecodeError):
        sink_mod.read_run(str(path))

    # a torn HEADER is an unreadable file, not a skippable line
    bad = tmp_path / "torn_header.jsonl"
    bad.write_text('{"schema": "dgc-telem')
    with pytest.raises(ValueError, match="unreadable telemetry header"):
        sink_mod.read_run_tolerant(str(bad))

    # a readable but future-versioned header still raises loudly
    fut = tmp_path / "future.jsonl"
    fut.write_text(json.dumps(dict(header, version=999)) + "\n")
    with pytest.raises(sink_mod.SchemaMismatchError):
        sink_mod.read_run_tolerant(str(fut))


# --------------------------------------------------------------------- #
# shard discovery + merge                                                #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_load_view_merges_hosts_and_rotations(tmp_path):
    run = _write_run(str(tmp_path), hosts=2, steps=20, torn=True,
                     rotate=True)
    shards = fleet.discover_shards(run)
    assert sorted(shards) == ["host0", "host1"]
    # rotation order: base shard before .1
    assert [os.path.basename(p) for p in shards["host0"]] == \
        ["telemetry.jsonl", "telemetry.1.jsonl"]

    view = fleet.load_view(run)
    assert sorted(view.hosts) == ["host0", "host1"]
    assert view.world == 4
    assert view.skipped == 1                      # host1's torn tail
    # host0's records span both rotated shards, in step order
    assert [r["step"] for r in view.steps] == list(range(20))
    assert [e["event"] for e in view.events] == ["engine_rebuild"]
    assert view.events[0]["host"] == "host0"

    with pytest.raises(FileNotFoundError):
        fleet.load_view(str(tmp_path / "nope"))


@pytest.mark.fast
def test_worker_series_prefers_columns_then_falls_back(tmp_path):
    run = _write_run(str(tmp_path), hosts=2, world=4, steps=5)
    series = fleet.worker_series(fleet.load_view(run), "w_clock")
    assert len(series) == 5 and len(series[0][1]) == 4

    # pre-fleet layout: per-host scalar columns only -> host-aligned
    old = tmp_path / "old"
    for h in range(2):
        hd = old / "telemetry" / f"host{h}"
        hd.mkdir(parents=True)
        lines = [json.dumps(registry.make_header({}))]
        for i in range(4):
            lines.append(json.dumps(
                {"step": i, "residual_mass": 100.0 + h}))
        (hd / "telemetry.jsonl").write_text("\n".join(lines) + "\n")
    series = fleet.worker_series(fleet.load_view(str(old)),
                                 "w_residual_mass")
    assert len(series) == 4
    assert series[0][1] == [100.0, 101.0]         # one value per host


# --------------------------------------------------------------------- #
# detectors                                                              #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_desync_detector_quiet_then_fires():
    rng = np.random.RandomState(7)
    healthy = [(i, list(100.0 * (1 + 0.03 * rng.randn(4))))
               for i in range(60)]
    assert fleet.detect_desync(healthy) == []

    # worker 2 walks away from the cohort mid-run
    bad = []
    for i, vals in healthy:
        vals = list(vals)
        if i >= 30:
            vals[2] *= 1.0 + 0.8 * (i - 29)
        bad.append((i, vals))
    alerts = fleet.detect_desync(bad)
    assert alerts and {a.worker for a in alerts} == {2}
    assert alerts[0].step >= 30 + 2               # min_hits consecutive
    assert alerts[0].deviation > alerts[0].band
    # the band is learned from history only: the diverging worker's own
    # huge deviations must not have inflated the band it tripped
    assert alerts[0].band < 1.0


#: w_residual_mass of a HEALTHY 8-worker cohort: the two-process drill
#: (tests/test_multiprocess.py::test_fleet_two_process_straggler) at 4
#: images a worker, its 14 steps from a cold start, rounded. Worker 5 sits
#: 0.47-0.57 off the median from step 9 on while the cohort's typical
#: deviation is 0.05-0.12, and comes back.
_HEALTHY_RESIDUAL_MASS = [
    [4.83, 4.03, 4.70, 4.69, 4.80, 5.27, 4.77, 4.40],
    [10.52, 6.58, 8.31, 8.99, 7.47, 10.71, 8.16, 9.56],
    [15.71, 8.68, 11.43, 14.46, 9.72, 15.55, 10.74, 15.35],
    [19.77, 13.79, 15.79, 19.48, 10.29, 20.34, 15.45, 18.58],
    [22.17, 19.48, 19.25, 23.90, 12.19, 24.64, 20.84, 20.97],
    [24.21, 21.64, 22.18, 25.33, 14.39, 29.68, 26.62, 22.53],
    [25.36, 23.89, 27.45, 27.36, 16.10, 33.69, 28.27, 23.38],
    [26.81, 25.57, 28.54, 28.75, 17.68, 37.66, 28.43, 24.81],
    [28.62, 27.24, 29.79, 30.98, 20.30, 40.81, 29.08, 26.77],
    [30.44, 27.75, 31.12, 32.16, 22.01, 43.19, 28.42, 27.81],
    [33.03, 28.76, 30.88, 32.01, 22.33, 45.11, 28.51, 26.32],
    [34.66, 28.27, 29.57, 30.81, 24.02, 45.79, 29.12, 23.36],
    [34.97, 26.74, 29.23, 29.57, 24.48, 45.20, 28.77, 24.96],
    [34.25, 26.51, 27.83, 26.77, 26.38, 43.77, 31.05, 27.95],
]


@pytest.mark.fast
def test_desync_floor_holds_a_healthy_excursion():
    """The band's floor is what a healthy worker may sit off the cohort
    median. A recorded healthy cohort stays quiet (the floor of 0.25 that
    the detector shipped with named worker 5); a worker whose state
    collapsed, and one that walks away, still alert, alone."""
    healthy = list(enumerate(_HEALTHY_RESIDUAL_MASS))
    assert fleet.detect_desync(healthy) == []
    old = fleet.detect_desync(healthy, band_floor=0.25)
    assert old and {a.worker for a in old} == {5}

    def spoiled(worker, factor_at):
        out = []
        for step, vals in healthy:
            vals = list(vals)
            vals[worker] *= factor_at(step)
            out.append((step, vals))
        return out

    # error-feedback state lost at step 6: the mass is rebuilt from zero
    collapsed = fleet.detect_desync(
        spoiled(3, lambda s: 1.0 if s < 6 else 0.02 * (s - 5)))
    assert collapsed and {a.worker for a in collapsed} == {3}
    assert collapsed[0].step == 6 + 2             # min_hits consecutive
    walked = fleet.detect_desync(
        spoiled(1, lambda s: 1.0 + 0.9 * max(0, s - 4)))
    assert walked and {a.worker for a in walked} == {1}


@pytest.mark.fast
def test_straggler_table_and_summary(tmp_path):
    run = _write_run(str(tmp_path), hosts=2, world=4, steps=30,
                     straggler=3)
    view = fleet.load_view(run)
    table = fleet.straggler_table(view)
    assert [r["worker"] for r in table][0] == 3
    assert table[0]["share"] > 1.5                # 90ms vs ~10ms cohort
    assert all(set(r) == {"worker", "mean_ms", "max_ms", "last_ms",
                          "share"} for r in table)
    summary = fleet.fleet_summary(view)
    assert summary["straggler"] == 3
    assert summary["straggler_gap"] > 50.0
    assert summary["desync_alerts"] == 0
    assert summary["num_hosts"] == 2 and summary["world"] == 4


# --------------------------------------------------------------------- #
# monitor                                                                #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_monitor_collect_and_renderers(tmp_path):
    run = _write_run(str(tmp_path), hosts=2, world=4, steps=30,
                     straggler=1)
    # a supervisor event stream under the run dir, as supervise.py
    # defaults it (--watch <run>/checkpoints)
    (tmp_path / "supervise_events.jsonl").write_text(
        json.dumps({"event": "launch", "t": 1.0, "launches": 1,
                    "run_id": "x", "cohort": {}}) + "\n"
        + json.dumps({"event": "relaunch", "t": 2.0, "launches": 2,
                      "rc": 75, "run_id": "x", "cohort": {}}) + "\n")

    snap = monitor.collect(run)
    assert snap["step"] == 29 and snap["world"] == 4
    assert snap["steps_per_s"] == pytest.approx(2.0)   # 0.5s t_host grid
    assert snap["compression_ratio"] == pytest.approx(20.0)  # 1000/50
    assert snap["supervise_launches"] == 2
    assert snap["last_supervise"]["event"] == "relaunch"
    assert snap["last_event"]["event"] == "engine_rebuild"

    om = monitor.render_openmetrics(snap)
    assert om.endswith("# EOF\n")
    # every gauge carries the run label (the supervise stream's run_id),
    # per-worker series add worker="i" alongside it
    for needle in ('dgc_worker_clock_ms{run="x",worker="0"}',
                   'dgc_worker_residual_mass{run="x",worker="3"}',
                   'dgc_step{run="x"}',
                   "dgc_straggler_gap_ms", "dgc_worker_skew",
                   "dgc_compression_ratio", "dgc_supervise_launches"):
        assert needle in om, needle
    assert snap["run_label"] == "x"
    # every family is HELP/TYPE'd exactly once
    helps = [l.split()[2] for l in om.splitlines()
             if l.startswith("# HELP")]
    assert len(helps) == len(set(helps))

    status = monitor.render_status(snap)
    assert "<- straggler" in status
    assert "worker  mean_ms" in status            # table header rendered
    assert "desync: quiet" in status
    assert "last supervise" in status


@pytest.mark.fast
def test_monitor_http_endpoint(tmp_path):
    run = _write_run(str(tmp_path), hosts=1, world=4, steps=10)
    server = monitor.ThreadingHTTPServer(
        ("127.0.0.1", 0), monitor._make_handler(monitor._Cache(run, 1.0)))
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            body = r.read().decode()
            assert r.headers["Content-Type"].startswith(
                "application/openmetrics-text")
        assert body.endswith("# EOF\n") and "dgc_step{" in body
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=10) as r:
            assert "dgc fleet monitor" in r.read().decode()
    finally:
        server.shutdown()


@pytest.mark.fast
def test_monitor_once_cli(tmp_path, capsys):
    run = _write_run(str(tmp_path), hosts=1, world=4, steps=10)
    assert monitor._main([run, "--once"]) == 0
    assert "dgc fleet monitor" in capsys.readouterr().out
    assert monitor._main([run, "--once", "--openmetrics"]) == 0
    assert capsys.readouterr().out.endswith("# EOF\n")
    assert monitor._main([str(tmp_path / "gone"), "--once"]) == 1


# --------------------------------------------------------------------- #
# supervisor event stamping                                              #
# --------------------------------------------------------------------- #

def _load_supervise():
    spec = importlib.util.spec_from_file_location(
        "supervise", os.path.join(ROOT, "scripts", "supervise.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.fast
def test_supervise_event_stamping_and_flush(tmp_path, monkeypatch):
    sup_mod = _load_supervise()
    monkeypatch.setenv("JAX_NUM_PROCESSES", "2")
    monkeypatch.setenv("JAX_PROCESS_ID", "0")
    events = tmp_path / "run" / "supervise_events.jsonl"
    sup = sup_mod.Supervisor(["true"], events=str(events))
    sup.event("launch", cmd=["true"])
    sup.launches = 1
    sup.event("relaunch", rc=75)
    # flushed per event: readable NOW, without any close/flush call
    recs = [json.loads(l) for l in events.read_text().splitlines()]
    assert [r["event"] for r in recs] == ["launch", "relaunch"]
    for r in recs:
        assert r["run_id"] == sup.run_id
        assert r["cohort"]["JAX_NUM_PROCESSES"] == "2"
    assert recs[1]["launches"] == 1

    # default stream location: next to the --watch dir, under the run dir
    assert sup_mod.default_events_path("/runs/exp/checkpoints") == \
        "/runs/exp/supervise_events.jsonl"
    assert sup_mod.default_events_path(None) is None
    # the monitor finds the same default
    assert monitor.supervise_events_path(str(tmp_path / "run")) == \
        str(events)


# --------------------------------------------------------------------- #
# slow fault token                                                       #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_faults_slow_token(monkeypatch):
    from dgc_tpu.resilience import faults
    assert faults.plan("slow:ms=40").slow_ms == 40
    assert faults.plan("slow").slow_ms == 100
    assert faults.plan("").slow_ms is None
    with pytest.raises(ValueError):
        faults.plan("sloow")
    monkeypatch.setenv(faults.ENV, "slow:ms=30")
    assert faults.armed()
    t0 = time.perf_counter()
    faults.maybe_slow()
    assert time.perf_counter() - t0 >= 0.025
    monkeypatch.setenv(faults.ENV, "")
    t0 = time.perf_counter()
    faults.maybe_slow()                           # unarmed: no sleep
    assert time.perf_counter() - t0 < 0.02


# --------------------------------------------------------------------- #
# in-graph: the packed gather on the 8-device mesh                       #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_gather_stats_identifies_straggler(mesh8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dgc_tpu.utils.compat import shard_map

    axes = tuple(mesh8.axis_names)
    clock_np = np.array([5, 5, 5, 260, 5, 5, 5, 5], np.float32)
    gn_np = np.arange(1, 9, dtype=np.float32)
    sh = NamedSharding(mesh8, P(axes))
    clock = jax.device_put(clock_np, sh)
    gnorm = jax.device_put(gn_np, sh)

    def worker(c, g):
        g = g.reshape(())
        stats = {"grad_norm": g, "residual_mass": 2.0 * g,
                 "payload_elems": jnp.float32(50.0)}
        return fleet.gather_stats(stats, axes, clock=c, total_elems=1000)

    telem_specs = {k: P() for k in ("grad_norm", "residual_mass",
                                    "payload_elems")}
    fleet_specs = {k: P() for k in registry.fleet_stat_names()}
    fn = jax.jit(shard_map(worker, mesh=mesh8, in_specs=(P(axes), P(axes)),
                           out_specs=(telem_specs, fleet_specs)))
    telem, flt = fn(clock, gnorm)

    # telemetry means replace the pmean exactly
    assert float(telem["grad_norm"]) == pytest.approx(float(gn_np.mean()))
    assert float(telem["residual_mass"]) == pytest.approx(
        2.0 * float(gn_np.mean()))
    # per-worker columns come back verbatim, every stat f32
    np.testing.assert_allclose(np.asarray(flt["w_clock"]), clock_np)
    np.testing.assert_allclose(np.asarray(flt["w_grad_norm"]), gn_np)
    assert all(np.asarray(v).dtype == np.float32 for v in flt.values())
    # straggler verdict + dispersion scalars
    assert int(flt["straggler"]) == 3
    assert float(flt["straggler_gap"]) == pytest.approx(255.0)
    assert np.asarray(flt["w_sent_ratio"]) == pytest.approx(0.05)
    clock_skew = 255.0 / clock_np.mean()
    assert float(flt["worker_skew"]) == pytest.approx(clock_skew, rel=1e-5)


@pytest.mark.fast
def test_make_clock_single_process(mesh8):
    import jax
    clk = fleet.make_clock(12.5, mesh8, 8)
    assert clk.shape == (8,) and clk.dtype == jax.numpy.float32
    np.testing.assert_allclose(np.asarray(clk), 12.5)
