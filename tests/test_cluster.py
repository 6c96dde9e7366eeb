"""Cluster observatory suite: cross-host journal merge (clock-offset /
wall-anchor / first-common-event alignment, dedup, rotated siblings),
causal incident reconstruction (episode grouping, bundle attribution,
orphan witnesses), bundle schema versioning, the incident CLI — and the slow two-process partition
incident acceptance soak (quorum side in-process with the sampler and a
burn-rate alert armed, minority side in a subprocess, the two journals
merged into ONE complete incident story with zero orphans).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from distributedarrays_tpu import telemetry as tm
from distributedarrays_tpu.resilience import (domains, elastic, faults,
                                              recovery)
from distributedarrays_tpu.telemetry import alerts, cluster, flight
from distributedarrays_tpu.telemetry.fixtures import telemetry_capture  # noqa: F401 (fixture)
from distributedarrays_tpu.train import Trainer, mlp_task

REPO = Path(__file__).resolve().parents[1]

_SPLIT = [[0, 1, 2, 3, 4], [5, 6, 7]]


@pytest.fixture(autouse=True)
def _clean_chaos():
    """Process-wide singletons pristine around every test (same guard as
    test_domains: fault plan, elastic manager, flight recorder,
    topology)."""
    faults.clear()
    elastic.manager().reset()
    flight._reset()
    domains.reset()
    yield
    faults.clear()
    elastic.manager().reset()
    flight._reset()
    domains.reset()


def _fast_policy(**kw):
    kw.setdefault("base_delay", 0.005)
    kw.setdefault("max_delay", 0.02)
    return recovery.RetryPolicy(**kw)


def _ev(host, pid, seq, t, cat, name, wall=None, **fields):
    e = {"host": host, "pid": pid, "seq": seq, "t": t, "cat": cat,
         "name": name, "tid": 1}
    if wall is not None:
        e["wall"] = wall
    e.update(fields)
    return e


def _cli(argv):
    from distributedarrays_tpu.telemetry.__main__ import main
    return main(argv)


def _write_journal(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


# ---------------------------------------------------------------------------
# merge_journals: the three alignment tiers
# ---------------------------------------------------------------------------


def test_merge_clock_edge_alignment():
    # host A's monotonic origin sits at A-wall 100.0; host B's at B-wall
    # 110.0; A's wall is AHEAD of B's by 8.0s (the clock edge).  B's
    # origin on A's wall timeline is therefore 118.0, so B t=2.0 is
    # simultaneous with A t=20.0.
    a = [_ev("hostA", 1, 0, 1.0, "train", "step", wall=101.0),
         _ev("hostA", 1, 1, 5.0, "train", "step", wall=105.0),
         _ev("hostA", 1, 2, 6.0, "multihost", "clock", wall=106.0,
             offsets={"1": {"offset_s": 8.0, "host": "hostB"}})]
    b = [_ev("hostB", 2, 0, 2.0, "train", "step", wall=112.0)]
    merged = cluster.merge_journals([a, b])
    by_host = {e["host"]: e for e in merged if e["name"] == "step"
               and e["seq"] == 0}
    # rebased so the earliest event (A t=1.0) is the origin
    assert by_host["hostA"]["t"] == pytest.approx(0.0)
    assert by_host["hostB"]["t"] == pytest.approx(19.0)
    assert by_host["hostB"]["t_local"] == pytest.approx(2.0)


def test_merge_wall_anchor_fallback():
    # no clock edge and no shared configuration event: pure wall-anchor
    # placement (anchors 100.0 vs 110.0 -> B shifts +10)
    a = [_ev("hostA", 1, 0, 1.0, "train", "step", wall=101.0),
         _ev("hostA", 1, 1, 5.0, "train", "step", wall=105.0)]
    b = [_ev("hostB", 2, 0, 2.0, "train", "step", wall=112.0)]
    merged = cluster.merge_journals([a, b])
    by_host = {e["host"]: e for e in merged if e["seq"] == 0}
    assert by_host["hostA"]["t"] == pytest.approx(0.0)
    assert by_host["hostB"]["t"] == pytest.approx(11.0)


def test_merge_common_event_overrides_skewed_walls():
    # both hosts journal the SAME fault plan; host B's wall clock is 6s
    # off NTP, so the wall anchors disagree — the shared configure event
    # (assumed simultaneous) must win over the skewed anchors
    plan_fields = {"seed": 7, "sites": 1}
    a = [_ev("hostA", 1, 0, 0.5, "train", "step", wall=100.5),
         _ev("hostA", 1, 1, 3.0, "faults", "configure", wall=103.0,
             **plan_fields)]
    b = [_ev("hostB", 2, 0, 9.0, "faults", "configure", wall=119.0,
             **plan_fields),
         _ev("hostB", 2, 1, 10.0, "train", "step", wall=120.0)]
    merged = cluster.merge_journals([a, b])
    confs = [e for e in merged if e["name"] == "configure"]
    assert len(confs) == 2
    assert confs[0]["t"] == pytest.approx(confs[1]["t"])
    assert confs[0]["t"] == pytest.approx(2.5)   # 3.0 rebased by A's 0.5


def test_merge_no_wall_stamps_uses_common_event():
    a = [_ev("hostA", 1, 0, 2.0, "domains", "configure",
             domains=2, ranks=8, sizes=[5, 3]),
         _ev("hostA", 1, 1, 4.0, "train", "step")]
    b = [_ev("hostB", 2, 0, 7.0, "domains", "configure",
             domains=2, ranks=8, sizes=[5, 3]),
         _ev("hostB", 2, 1, 8.0, "train", "step")]
    merged = cluster.merge_journals([a, b])
    confs = [e for e in merged if e["name"] == "configure"]
    assert confs[0]["t"] == pytest.approx(confs[1]["t"])
    steps = {e["host"]: e["t"] for e in merged if e["name"] == "step"}
    assert steps["hostB"] == pytest.approx(steps["hostA"] - 1.0)


def test_merge_dedups_shared_events_and_sorts():
    a = [_ev("hostA", 1, 0, 1.0, "train", "step", wall=101.0),
         _ev("hostA", 1, 1, 2.0, "train", "step", wall=102.0)]
    # the same journal fed twice (a copied file): every (host, pid, seq)
    # appears exactly once
    merged = cluster.merge_journals([a, list(a)])
    assert len(merged) == 2
    assert [e["seq"] for e in merged] == [0, 1]
    assert merged[0]["t"] <= merged[1]["t"]


def test_merge_reads_rotated_sibling_oldest_first(tmp_path):
    p = tmp_path / "j.jsonl"
    _write_journal(str(p) + ".1",
                   [_ev("h", 1, 0, 1.0, "train", "step", wall=101.0)])
    _write_journal(str(p),
                   [_ev("h", 1, 1, 2.0, "train", "step", wall=102.0)])
    merged = cluster.merge_journals([str(p)])
    assert [e["seq"] for e in merged] == [0, 1]


# ---------------------------------------------------------------------------
# reconstruct_incidents
# ---------------------------------------------------------------------------


_I1 = "inc-hostA-1-1"
_I2 = "inc-hostB-2-1"


def _partition_story():
    """A merged two-host timeline of one 5/3 partition: quorum side
    recovers, minority side exits typed; the injection and the serve
    drain are UNSTAMPED (recorded outside the id windows' owners)."""
    return [
        _ev("hostA", 1, 0, 10.0, "faults", "fire", wall=1000.0,
            action="partition", site="train.step"),
        _ev("hostA", 1, 1, 10.2, "multihost", "quorum", wall=1000.2,
            verdict="quorum", side=[0, 1, 2, 3, 4], lost=[5, 6, 7],
            incident=_I1),
        _ev("hostA", 1, 2, 10.3, "incident", "begin", wall=1000.3,
            kind="partition", incident=_I1),
        _ev("hostA", 1, 3, 10.4, "recovery", "failure", wall=1000.4,
            attempt=1, verdict="partition", retrying=True, incident=_I1),
        _ev("hostA", 1, 4, 10.5, "checkpoint", "restore_peer",
            wall=1000.5, step=4, incident=_I1),
        _ev("hostA", 1, 5, 10.6, "elastic", "shrink", wall=1000.6,
            live=5, moved=3, incident=_I1),
        _ev("hostA", 1, 6, 10.9, "recovery", "recovered", wall=1000.9,
            attempts=1, incident=_I1),
        _ev("hostA", 1, 7, 11.0, "incident", "end", wall=1001.0,
            resolution="recovered", incident=_I1),
        _ev("hostB", 2, 0, 10.35, "incident", "begin", wall=1000.35,
            kind="partition", incident=_I2),
        _ev("hostB", 2, 1, 10.45, "multihost", "quorum", wall=1000.45,
            verdict="minority", side=[5, 6, 7], lost=[0, 1, 2, 3, 4],
            incident=_I2),
        _ev("hostB", 2, 2, 10.55, "recovery", "minority_exit",
            wall=1000.55, side=[5, 6, 7], lost=[0, 1, 2, 3, 4],
            incident=_I2),
        _ev("hostB", 2, 3, 10.65, "incident", "end", wall=1000.65,
            resolution="minority_exit", incident=_I2),
        _ev("hostB", 2, 4, 10.75, "serve", "partition_drain",
            wall=1000.75, side=[5, 6, 7], lost=[0, 1, 2, 3, 4],
            endpoint="echo"),
    ]


def test_reconstruct_one_episode_from_two_sides():
    report = cluster.reconstruct_incidents(_partition_story())
    assert report["events_total"] == 13
    assert len(report["incidents"]) == 1
    ep = report["incidents"][0]
    assert sorted(ep["ids"]) == [_I1, _I2]
    assert ep["kinds"] == ["partition"]
    assert ep["hosts"] == ["hostA", "hostB"]
    assert ep["resolutions"] == {_I1: "recovered", _I2: "minority_exit"}
    whats = [s["what"] for s in ep["steps"]]
    assert whats[0] == "partition injected at train.step"
    assert any("quorum verdict quorum" in w for w in whats)
    assert any("quorum verdict minority" in w for w in whats)
    assert any("restored step 4 from peer replicas (zero disk reads)"
               in w for w in whats)
    assert any(w.startswith("shrank to 5 live devices") for w in whats)
    assert any("recovered after 1 attempts" in w for w in whats)
    assert any("exiting typed" in w for w in whats)
    assert any("server drained typed" in w for w in whats)
    # steps come out time-ordered
    ts = [s["t"] for s in ep["steps"]]
    assert ts == sorted(ts)
    assert report["unattributed_recovery_events"] == 0


def test_reconstruct_separate_windows_stay_separate_episodes():
    late = [_ev("hostA", 1, 10, 500.0, "incident", "begin", wall=1490.0,
                kind="device_loss", incident="inc-hostA-1-9"),
            _ev("hostA", 1, 11, 500.5, "incident", "end", wall=1490.5,
                resolution="recovered", incident="inc-hostA-1-9")]
    report = cluster.reconstruct_incidents(_partition_story() + late)
    assert len(report["incidents"]) == 2
    kinds = {tuple(ep["kinds"]) for ep in report["incidents"]}
    assert kinds == {("partition",), ("device_loss",)}


def test_reconstruct_counts_orphan_recovery_events():
    events = _partition_story() + [
        _ev("hostA", 1, 20, 900.0, "recovery", "failure", wall=1900.0,
            attempt=1, verdict="oom", retrying=False)]
    report = cluster.reconstruct_incidents(events)
    assert report["unattributed_recovery_events"] == 1


def _bundle(path, *, incident=None, host="hostB", pid=2, wall=1000.7,
            version=flight.SCHEMA_VERSION, kind="da_tpu_postmortem"):
    b = {"kind": kind, "reason": "crash", "classification": "partition",
         "host": host, "pid": pid, "wall": wall}
    if version is not None:
        b["schema_version"] = version
    if incident is not None:
        b["incident"] = incident
    with open(path, "w") as f:
        json.dump(b, f)
    return b


def test_bundle_attribution_by_id_window_and_orphan(tmp_path):
    p_id = tmp_path / "by_id.json"
    p_win = tmp_path / "by_window.json"
    p_orphan = tmp_path / "orphan.json"
    _bundle(p_id, incident=_I2)
    _bundle(p_win)                       # unstamped: host/pid + wall fit
    _bundle(p_orphan, wall=5000.0)       # nowhere near the episode
    bundles = cluster.load_bundles([str(tmp_path)])
    report = cluster.reconstruct_incidents(_partition_story(), bundles)
    assert report["bundles_total"] == 3
    assert report["bundles_attributed"] == 2
    assert report["bundles_unattributed"] == [str(p_orphan)]
    ep = report["incidents"][0]
    got = sorted(b["path"] for b in ep["bundles"])
    assert got == sorted([str(p_id), str(p_win)])


def test_load_bundles_schema_versions(tmp_path):
    _bundle(tmp_path / "v1.json", version=None)       # pre-version era
    _bundle(tmp_path / "v2.json")
    (tmp_path / "not_a_bundle.json").write_text('{"kind": "other"}')
    (tmp_path / "garbage.json").write_text("not json at all")
    loaded = cluster.load_bundles([str(tmp_path)])
    assert len(loaded) == 2
    assert {b.get("schema_version", 1) for b in loaded} == \
        {1, flight.SCHEMA_VERSION}
    _bundle(tmp_path / "v99.json", version=99)
    with pytest.raises(ValueError, match="upgrade distributedarrays_tpu"):
        cluster.load_bundles([str(tmp_path)])


def test_incident_trace_threads_flow_arrows():
    events = _partition_story()
    trace = cluster.incident_trace(events)
    flows = [e for e in trace["traceEvents"]
             if e.get("cat") == "incident" and e.get("ph") in "stf"]
    assert len(flows) >= 2
    assert flows[0]["ph"] == "s"
    assert flows[-1]["ph"] == "f" and flows[-1]["bp"] == "e"
    assert len({e["id"] for e in flows}) == 1     # one flow per episode
    assert all(e["ph"] == "t" for e in flows[1:-1])


# ---------------------------------------------------------------------------
# incident lifecycle: the recovery executor mints / closes ids
# ---------------------------------------------------------------------------


def test_quorum_recovery_mints_and_closes_incident(telemetry_capture,
                                                   tmp_path):
    tm_ = telemetry_capture
    domains.configure(_SPLIT)
    faults.configure(seed=9, plan=[
        {"site": "train.step", "match": {"step": 3}, "action": "partition",
         "at": 1, "groups": _SPLIT, "observer": 0}])
    with Trainer(mlp_task(batch_size=56), ckpt_dir=tmp_path, save_every=2,
                 policy=_fast_policy(), peer_replicas=True) as t:
        res = t.fit(5)
    assert len(res["losses"]) == 5
    incs = list(tm_.events("incident"))
    begins = [e for e in incs if e["name"] == "begin"]
    ends = [e for e in incs if e["name"] == "end"]
    assert len(begins) == 1 and begins[0]["kind"] == "partition"
    assert len(ends) == 1 and ends[0]["resolution"] == "recovered"
    inc = begins[0]["incident"]
    assert inc.startswith("inc-")
    # the causal neighbours got stamped with the same id
    fails = [e for e in tm_.events("recovery") if e["name"] == "failure"]
    assert fails and all(e.get("incident") == inc for e in fails)
    assert tm_.current_incident() is None         # closed after recovery


def test_minority_exit_closes_incident_and_stamps_bundle(telemetry_capture,
                                                         tmp_path):
    tm_ = telemetry_capture
    domains.configure(_SPLIT)
    faults.configure(seed=9, plan=[
        {"site": "train.step", "match": {"step": 3}, "action": "partition",
         "at": 1, "groups": _SPLIT, "observer": 6}])
    with Trainer(mlp_task(batch_size=56), ckpt_dir=tmp_path, save_every=2,
                 policy=_fast_policy(), peer_replicas=True) as t:
        with pytest.raises(recovery.MinorityPartitionExit) as ei:
            t.fit(5)
    assert ei.value.incident and ei.value.incident.startswith("inc-")
    ends = [e for e in tm_.events("incident") if e["name"] == "end"]
    assert len(ends) == 1 and ends[0]["resolution"] == "minority_exit"
    # the flight bundle carries the schema version and the incident id
    bundles = cluster.load_bundles([os.path.dirname(tm_.journal_path())])
    assert len(bundles) == 1
    assert bundles[0]["schema_version"] == flight.SCHEMA_VERSION
    assert bundles[0]["incident"] == ei.value.incident


# ---------------------------------------------------------------------------
# the incident CLI
# ---------------------------------------------------------------------------


def _story_journals(tmp_path):
    story = _partition_story()
    j1 = tmp_path / "hostA.jsonl"
    j2 = tmp_path / "hostB.jsonl"
    _write_journal(j1, [e for e in story if e["host"] == "hostA"])
    _write_journal(j2, [e for e in story if e["host"] == "hostB"])
    return str(j1), str(j2)


def test_cli_incident_text_json_and_trace(tmp_path, capsys):
    j1, j2 = _story_journals(tmp_path)
    assert _cli(["incident", j1, j2]) == 0
    out = capsys.readouterr().out
    assert "incident 1: partition" in out
    assert _I1 in out and _I2 in out
    assert "partition injected at train.step" in out
    assert f"{_I2}=minority_exit" in out

    trace_path = tmp_path / "trace.json"
    assert _cli(["incident", j1, j2, "--json",
                 "--trace", str(trace_path)]) == 0
    report = json.loads(capsys.readouterr().out)
    assert len(report["incidents"]) == 1
    assert sorted(report["incidents"][0]["ids"]) == [_I1, _I2]
    trace = json.loads(trace_path.read_text())
    assert any(e.get("cat") == "incident" for e in trace["traceEvents"])


def test_cli_incident_strict_bundles_gate(tmp_path, capsys):
    j1, j2 = _story_journals(tmp_path)
    bdir = tmp_path / "bundles"
    bdir.mkdir()
    _bundle(bdir / "attributed.json", incident=_I2)
    assert _cli(["incident", j1, j2, "--bundles", str(bdir),
                 "--strict-bundles"]) == 0
    capsys.readouterr()
    _bundle(bdir / "orphan.json", wall=5000.0)
    assert _cli(["incident", j1, j2, "--bundles", str(bdir),
                 "--strict-bundles"]) == 1
    err = capsys.readouterr().err
    assert "orphaned bundle" in err and "incomplete" in err


def test_cli_incident_refuses_newer_bundle_schema(tmp_path, capsys):
    j1, j2 = _story_journals(tmp_path)
    bdir = tmp_path / "bundles"
    bdir.mkdir()
    _bundle(bdir / "future.json", version=flight.SCHEMA_VERSION + 1)
    assert _cli(["incident", j1, j2, "--bundles", str(bdir)]) == 2
    err = capsys.readouterr().err
    assert "schema_version" in err and "upgrade" in err


def test_cli_incident_rc2_on_empty_journal(tmp_path, capsys):
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert _cli(["incident", str(empty)]) == 2
    assert "journal is empty" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the two-process partition incident acceptance soak
# ---------------------------------------------------------------------------

_MINORITY_SCRIPT = """
import _cpu_harness; _cpu_harness.force_cpu_mesh()
import sys
from distributedarrays_tpu.resilience import domains, faults, recovery
from distributedarrays_tpu.train import Trainer, mlp_task
domains.configure([[0, 1, 2, 3, 4], [5, 6, 7]])
faults.configure(seed=42, plan=[
    {"site": "train.step", "match": {"step": 5}, "action": "partition",
     "at": 1, "groups": [[0, 1, 2, 3, 4], [5, 6, 7]], "observer": 6}])
pol = recovery.RetryPolicy(base_delay=0.005, max_delay=0.02)
t = Trainer(mlp_task(batch_size=56), ckpt_dir=sys.argv[1], save_every=2,
            policy=pol, peer_replicas=True)
try:
    t.fit(8)
    print("UNEXPECTED_COMPLETE")
except recovery.MinorityPartitionExit as e:
    print("MINORITY_OK", e.incident)
finally:
    t.close()
"""


@pytest.mark.slow
def test_partition_incident_observatory_soak(telemetry_capture, tmp_path):
    """The PR's acceptance soak: the 5/3 partition observed from BOTH
    sides — minority in a subprocess (own journal + flight dir), quorum
    in-process with the health sampler running and a fast-burn serve p99
    alert armed.  Merging the two journals must yield ONE complete
    incident story: injection, both quorum verdicts, a peer-first
    restore with zero disk reads, the shrink, the retry, the minority's
    single bundle — no orphans — and the alert fires during the episode
    and clears after."""
    tm_ = telemetry_capture
    bdir = tmp_path / "bundles"
    bdir.mkdir()
    j2 = tmp_path / "minority.jsonl"

    # ---- minority side, its own process (slow: imports jax) ----------
    r = subprocess.run(
        [sys.executable, "-c", _MINORITY_SCRIPT,
         str(tmp_path / "ckpt_minority")],
        cwd=str(REPO), capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "DA_TPU_TELEMETRY": "1",
             "DA_TPU_TELEMETRY_JOURNAL": str(j2),
             "DA_TPU_FLIGHT_DIR": str(bdir)})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MINORITY_OK inc-" in r.stdout

    # ---- quorum side, in-process, sampler + alert armed --------------
    assert alerts.start_sampler(interval_s=0.05)
    mgr = alerts.AlertManager([alerts.AlertRule(
        "serve_p99", lambda: tm_.gauge_value("serve.request_p99_s"),
        threshold=0.5, fast_window_s=0.5, slow_window_s=1.0)])
    try:
        domains.configure(_SPLIT)
        faults.configure(seed=42, plan=[
            {"site": "train.step", "match": {"step": 5},
             "action": "partition", "at": 1, "groups": _SPLIT,
             "observer": 0}])
        d0 = tm_.counter_value("checkpoint.restore_source", source="disk")
        with Trainer(mlp_task(batch_size=56),
                     ckpt_dir=tmp_path / "ckpt_quorum", save_every=2,
                     policy=_fast_policy(), peer_replicas=True) as t:
            res = t.fit(8)
        assert len(res["losses"]) == 8
        # the SLO breach rides the incident window: fast burn fires ...
        tm_.set_gauge("serve.request_p99_s", 2.0)
        state = mgr.evaluate(now=100.0)
        assert state["serve_p99"] is True
        # ... and the recovery clears it
        tm_.set_gauge("serve.request_p99_s", 0.01)
        mgr.evaluate(now=100.4)
        state = mgr.evaluate(now=100.7)
        assert state["serve_p99"] is False
        import time
        time.sleep(0.15)                 # at least one sampler tick
    finally:
        alerts.stop_sampler()

    # ---- merge the two sides and reconstruct -------------------------
    merged = cluster.merge_journals([tm_.journal_path(), str(j2)])
    hosts_pids = {(e.get("host"), e.get("pid")) for e in merged}
    assert len(hosts_pids) == 2          # two streams, one per process
    bundles = cluster.load_bundles(
        [str(bdir), os.path.dirname(tm_.journal_path())])
    assert len(bundles) == 2             # one crash bundle per side
    # generous slack: the two runs execute sequentially, so their id
    # windows sit tens of seconds apart on the merged wall timeline
    report = cluster.reconstruct_incidents(merged, bundles, slack_s=60.0)
    assert report["bundles_total"] == 2
    assert report["bundles_attributed"] == 2
    assert report["bundles_unattributed"] == []
    assert report["unattributed_recovery_events"] == 0
    all_ids = sorted(i for ep in report["incidents"] for i in ep["ids"])
    assert len(all_ids) == 2             # one id minted per side
    whats = [s["what"] for ep in report["incidents"]
             for s in ep["steps"]]
    assert any("partition injected" in w for w in whats)
    assert any("quorum verdict quorum" in w for w in whats)
    assert any("quorum verdict minority" in w for w in whats)
    assert any("restored" in w and "peer replicas (zero disk reads)" in w
               for w in whats)
    assert any(w.startswith("shrank to") for w in whats)
    assert any("exiting typed" in w for w in whats)
    assert any("alert serve_p99 firing" in w for w in whats)
    # zero disk restores on the quorum side, and the alert CLEARED after
    assert tm_.counter_value("checkpoint.restore_source",
                             source="disk") == d0
    clear = [e for e in merged if e.get("cat") == "alert"
             and e.get("state") == "cleared"]
    assert clear, "the serve_p99 alert never cleared"
    # the sampler left health samples on the quorum journal
    assert any(e.get("cat") == "sample" and e.get("name") == "health"
               for e in merged)
