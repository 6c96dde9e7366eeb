"""Failure-domain resilience suite: topology + buddy placement, the
quorum rule, partition/slow_link chaos actions, peer-replicated
checkpoints, whole-domain elastic operations, the partition verdict in
recovery, minority-side serve drain — and the partition acceptance soak
(a seeded 5/3 split mid-training: quorum side shrinks to its domains and
restores every shard from peer replicas with ZERO disk reads, bit-equal
post-resume losses; minority side exits typed with exactly one bundle).
"""

import numpy as np
import pytest

import distributedarrays_tpu as dat
from distributedarrays_tpu import serve, telemetry as tm
from distributedarrays_tpu.parallel import multihost
from distributedarrays_tpu.serve import Draining
from distributedarrays_tpu.resilience import (domains, elastic, faults,
                                              recovery)
from distributedarrays_tpu.telemetry import flight
from distributedarrays_tpu.telemetry import memory as tmem
from distributedarrays_tpu.train import Trainer, mlp_task
from distributedarrays_tpu.utils.checkpoint import (
    CheckpointIntegrityError, CheckpointManager, PeerReplicaStore,
    PeerReplicaUnavailable)


@pytest.fixture(autouse=True)
def _clean_chaos():
    """Process-wide singletons (fault plan, elastic manager, flight
    recorder, domain topology) pristine around every test."""
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


_SPLIT = [[0, 1, 2, 3, 4], [5, 6, 7]]


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------


def test_topology_from_sizes_and_json():
    t = domains.configure("5,3")
    assert t.domains() == {0: [0, 1, 2, 3, 4], 1: [5, 6, 7]}
    t = domains.configure("[[0,2],[1,3]]")
    assert t.domains() == {0: [0, 2], 1: [1, 3]}
    assert t.domain_of(3) == 1


def test_topology_rejects_duplicates_and_empty():
    with pytest.raises(ValueError, match="more than one"):
        domains.DomainTopology([[0, 1], [1, 2]])
    with pytest.raises(ValueError, match="non-empty"):
        domains.DomainTopology([[], []])


def test_topology_default_is_one_domain_per_process():
    # single-controller CPU mesh: every device reports process 0, so the
    # default collapses to exactly one domain covering all ranks
    t = domains.topology()
    assert len(t.domains()) == 1
    assert t.ranks() == list(range(len(t.ranks())))


def test_domain_of_unknown_rank_raises():
    domains.configure(_SPLIT)
    with pytest.raises(KeyError, match="not in the domain topology"):
        domains.domain_of(99)


def test_live_domains_omits_empty():
    t = domains.configure(_SPLIT)
    assert t.live_domains([0, 1, 7]) == {0: [0, 1], 1: [7]}
    assert t.live_domains([0, 1]) == {0: [0, 1]}


# ---------------------------------------------------------------------------
# buddy placement invariant
# ---------------------------------------------------------------------------


def test_buddy_map_is_cross_domain_with_two_live_domains():
    topo = domains.configure(_SPLIT)
    bmap = domains.buddy_map(live_ranks=range(8))
    assert set(bmap) == set(range(8))
    for r, b in bmap.items():
        assert topo.domain_of(r) != topo.domain_of(b), (r, b)
    assert domains.is_cross_domain(bmap)


def test_buddy_map_rebuddies_after_uneven_shrink():
    # domain 1 shrinks to a single survivor: every domain-0 rank must
    # re-buddy onto it (cross-domain preserved), and it buddies back
    topo = domains.configure(_SPLIT)
    live = [0, 1, 2, 3, 4, 7]
    bmap = domains.buddy_map(live_ranks=live)
    assert set(bmap) == set(live)
    for r in (0, 1, 2, 3, 4):
        assert bmap[r] == 7
    assert bmap[7] in (0, 1, 2, 3, 4)
    assert domains.is_cross_domain(bmap, topo)


def test_buddy_map_degrades_in_domain_when_one_domain_left():
    domains.configure(_SPLIT)
    bmap = domains.buddy_map(live_ranks=[0, 1, 2])   # domain 1 fully gone
    # in-domain ring: the only placement that still exists — flagged by
    # is_cross_domain so callers can see the degraded state
    assert bmap == {0: 1, 1: 2, 2: 0}
    assert not domains.is_cross_domain(bmap)
    assert domains.buddy_map(live_ranks=[3]) == {3: 3}   # lone rank


def test_buddy_map_is_deterministic_per_live_set():
    domains.configure(_SPLIT)
    for live in ([0, 1, 2, 5, 6], [0, 4, 7], list(range(8))):
        assert domains.buddy_map(live_ranks=live) == \
            domains.buddy_map(live_ranks=list(reversed(live)))


# ---------------------------------------------------------------------------
# the quorum rule
# ---------------------------------------------------------------------------


def test_majority_side_strict_majority_wins():
    q = domains.majority_side(_SPLIT, observer=0)
    assert q == {"verdict": "quorum", "side": [0, 1, 2, 3, 4],
                 "lost": [5, 6, 7]}
    q = domains.majority_side(_SPLIT, observer=6)
    assert q["verdict"] == "minority" and q["side"] == [5, 6, 7]


def test_majority_side_tie_breaks_toward_coordinator():
    groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert domains.majority_side(groups, 1)["verdict"] == "quorum"
    assert domains.majority_side(groups, 5)["verdict"] == "minority"
    # an explicit coordinator moves the tiebreak with it
    assert domains.majority_side(groups, 5,
                                 coordinator=4)["verdict"] == "quorum"


def test_majority_side_survives_coordinator_loss():
    # the coordinator (rank 0) lands on the SMALL side: the strict
    # majority must still win — the coordinator-loss fallback
    groups = [[0, 1], [2, 3, 4, 5, 6, 7]]
    assert domains.majority_side(groups, 3)["verdict"] == "quorum"
    assert domains.majority_side(groups, 0)["verdict"] == "minority"


def test_majority_side_expected_total_counts_silent_ranks():
    # 3 of 8 expected ranks answering is NOT a majority even if they are
    # the largest connected component observed
    q = domains.majority_side([[0, 1, 2]], 0, expected_total=8)
    assert q["verdict"] == "minority"


# ---------------------------------------------------------------------------
# partition / slow_link fault actions
# ---------------------------------------------------------------------------


def test_partition_spec_requires_groups():
    with pytest.raises(ValueError, match="needs 'groups'"):
        faults.FaultSpec.from_dict({"site": "train.step",
                                    "action": "partition"}, 0)


def test_partition_action_downs_far_side_and_heals():
    faults.configure(seed=3, plan=[
        {"site": "spmd.collective", "action": "partition", "at": 1,
         "groups": _SPLIT, "observer": 0}])
    with pytest.raises(faults.InjectedPartition) as ei:
        faults.check("spmd.collective")
    assert ei.value.lost == [5, 6, 7]
    st = faults.partition_state()
    assert st["side"] == [0, 1, 2, 3, 4] and st["lost"] == [5, 6, 7]
    assert elastic.manager().probe()["down"] == [5, 6, 7]
    faults.heal_partition()
    assert faults.partition_state() is None
    assert elastic.manager().probe()["down"] == []


def test_partition_revive_after_clears_state():
    faults.configure(seed=3, plan=[
        {"site": "train.step", "action": "partition", "at": 1,
         "groups": _SPLIT, "observer": 0, "revive_after": 2}])
    with pytest.raises(faults.InjectedPartition):
        faults.check("train.step")
    m = elastic.manager()
    assert m.probe()["down"] == [5, 6, 7]    # tick 1
    assert m.probe()["down"] == []           # tick 2: revived
    assert faults.partition_state() is None


def test_slow_link_delay_is_seeded_and_bounded():
    faults.configure(seed=11, plan=[
        {"site": "reshard.chunk", "action": "slow_link", "at": 1,
         "count": 3, "hang_s": 0.01}])
    h0 = len(faults.history())
    for _ in range(3):
        faults.check("reshard.chunk")        # sleeps, never raises
    fired = faults.history()[h0:]
    assert [f["action"] for f in fired] == ["slow_link"] * 3
    # replay: same seed, same plan -> identical injection history
    faults.configure(seed=11, plan=[
        {"site": "reshard.chunk", "action": "slow_link", "at": 1,
         "count": 3, "hang_s": 0.01}])
    for _ in range(3):
        faults.check("reshard.chunk")
    again = faults.history()[-3:]
    assert [(f["site"], f["invocation"]) for f in again] == \
        [(f["site"], f["invocation"]) for f in fired]
    spec = faults.FaultSpec.from_dict(
        {"site": "x", "action": "slow_link", "hang_s": 0.5}, 0)
    d = faults.slow_link_delay(spec)
    assert 0.25 <= d < 0.5                   # [0.5, 1.0) * hang_s


def test_cross_domain_reshard_survives_seeded_slow_link(rng):
    # the hierarchical-tier chaos gate: a seeded slow_link firing at the
    # reshard chaos site stalls (never kills) a CROSS-domain collective
    # chain — the mesh-axis transpose must still lower through
    # collectives (no silent device_put demotion) and land bit-identical
    # to the oracle, with the firing on the chaos record
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributedarrays_tpu import layout as L
    from distributedarrays_tpu.parallel import reshard as R

    domains.configure("4,4")
    # the plan cache is keyed on the layout pair, not on the domain
    # topology (ROADMAP Queue 3 item 16): a plan of this pair made by
    # another file's test on the same worker would carry its stamps
    R._plan_cached.cache_clear()
    faults.configure(seed=1234, plan=[
        {"site": "reshard.chunk", "action": "slow_link", "at": 1,
         "count": -1, "hang_s": 0.01}])
    A = rng.standard_normal((48, 48)).astype(np.float32)
    mesh = L.mesh_for(list(range(8)), (4, 2))
    src = NamedSharding(mesh, P("d0", "d1"))
    dst = NamedSharding(mesh, P("d1", "d0"))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    # the transpose touches the major mesh axis, so its gather/a2a
    # sub-groups span the 4|4 domain boundary: a genuine DCN-path move
    assert plan.strategy == "chain" and plan.cross_bytes > 0
    h0 = len(faults.history())
    y = R.reshard(x, dst)
    fired = [f for f in faults.history()[h0:]
             if f["action"] == "slow_link"]
    assert fired and fired[0]["site"] == "reshard.chunk"
    assert y.sharding.is_equivalent_to(dst, y.ndim)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(jax.device_put(A, dst)))


# ---------------------------------------------------------------------------
# quorum_assess + elastic integration
# ---------------------------------------------------------------------------


def test_quorum_assess_healthy_without_evidence():
    domains.configure(_SPLIT)
    out = multihost.quorum_assess()
    assert out["verdict"] == "healthy" and out["lost"] == []


def test_quorum_assess_reads_injected_partition():
    domains.configure(_SPLIT)
    faults.configure(seed=1, plan=[
        {"site": "train.step", "action": "partition", "at": 1,
         "groups": _SPLIT, "observer": 6}])
    with pytest.raises(faults.InjectedPartition):
        faults.check("train.step")
    out = multihost.quorum_assess()
    assert out["verdict"] == "minority"
    assert out["side"] == [5, 6, 7]


def test_probe_caches_partition_verdict():
    domains.configure(_SPLIT)
    m = elastic.manager()
    assert m.partition_verdict()["verdict"] == "healthy"
    faults.configure(seed=1, plan=[
        {"site": "train.step", "action": "partition", "at": 1,
         "groups": _SPLIT, "observer": 0}])
    with pytest.raises(faults.InjectedPartition):
        faults.check("train.step")
    out = m.probe()
    assert out["partition"]["verdict"] == "quorum"
    assert m.partition_verdict()["verdict"] == "quorum"
    m.reset()
    assert m.partition_verdict()["verdict"] == "healthy"


def test_whole_domain_shrink_and_grow():
    domains.configure(_SPLIT)
    d = dat.distribute(np.arange(64.0).reshape(8, 8))
    m = elastic.manager()
    out = m.shrink(domain=1)
    assert out["live"] == [0, 1, 2, 3, 4]
    # placement invariant: re-layout keeps every chunk out of the dying
    # domain
    assert {int(p) for p in d.pids.flat} <= {0, 1, 2, 3, 4}
    np.testing.assert_array_equal(np.asarray(d),
                                  np.arange(64.0).reshape(8, 8))
    out = m.grow(domain=1)
    assert out["live"] == list(range(8))
    assert {5, 6, 7} & {int(p) for p in d.pids.flat}
    np.testing.assert_array_equal(np.asarray(d),
                                  np.arange(64.0).reshape(8, 8))
    d.close()


# ---------------------------------------------------------------------------
# peer-replicated checkpoints
# ---------------------------------------------------------------------------


def test_peer_replica_round_trip_all_live(tmp_path):
    domains.configure(_SPLIT)
    d = dat.distribute(np.arange(32.0).reshape(4, 8))
    reps = PeerReplicaStore()
    mgr = CheckpointManager(tmp_path, async_save=False, replicas=reps)
    mgr.save(1, {"w": d, "n": 7})
    assert reps.steps() == [1]
    out = mgr.restore()
    assert out["n"] == 7
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(32.0).reshape(4, 8))
    out["w"].close()
    d.close()
    mgr.close()


def test_peer_replica_serves_after_domain_loss_zero_disk_reads(tmp_path):
    domains.configure(_SPLIT)
    d = dat.distribute(np.arange(64.0).reshape(8, 8))
    reps = PeerReplicaStore()
    mgr = CheckpointManager(tmp_path, async_save=False, replicas=reps)
    mgr.save(2, {"w": d})
    m = elastic.manager()
    for r in (5, 6, 7):
        m.mark_down(r)
    dr0 = tm.counter_value("checkpoint.disk_reads")
    p0 = tm.counter_value("checkpoint.restore_source", source="peer")
    out = mgr.restore()
    assert tm.counter_value("checkpoint.disk_reads") == dr0    # ZERO reads
    assert tm.counter_value("checkpoint.restore_source",
                            source="peer") == p0 + 1
    assert tm.counter_value("checkpoint.peer_fetches") >= 1
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(64.0).reshape(8, 8))
    out["w"].close()
    d.close()
    mgr.close()


def test_peer_replica_unavailable_falls_back_to_disk(tmp_path):
    # owner AND holder of some chunk down (both domains hit): the
    # replica tier reports unavailable and restore falls back to disk
    domains.configure(_SPLIT)
    d = dat.distribute(np.arange(64.0).reshape(8, 8))
    reps = PeerReplicaStore()
    mgr = CheckpointManager(tmp_path, async_save=False, replicas=reps)
    mgr.save(1, {"w": d})
    with pytest.raises(PeerReplicaUnavailable):
        reps.fetch(1, live_ranks=[1, 2])     # rank 0 and its holder gone
    dr0 = tm.counter_value("checkpoint.disk_reads")
    m = elastic.manager()
    for r in (0, 5, 6, 7):
        m.mark_down(r)
    out = mgr.restore()                      # disk fallback
    assert tm.counter_value("checkpoint.disk_reads") == dr0 + 1
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(64.0).reshape(8, 8))
    out["w"].close()
    d.close()
    mgr.close()


def test_peer_replica_crc_mismatch_raises_and_evicts(tmp_path):
    domains.configure(_SPLIT)
    reps = PeerReplicaStore()
    mgr = CheckpointManager(tmp_path, async_save=False, replicas=reps)
    mgr.save(1, {"w": np.arange(8.0)})
    # flip a byte inside the stored replica chunk
    rec = reps._steps[1]
    k = next(iter(rec["chunks"]))
    data = bytearray(rec["chunks"][k]["data"])
    data[0] ^= 0xFF
    rec["chunks"][k]["data"] = bytes(data)
    with pytest.raises(CheckpointIntegrityError):
        reps.fetch(1, live_ranks=range(8))
    out = mgr.restore()                      # falls back to disk, evicts
    assert reps.steps() == []
    np.testing.assert_array_equal(out["w"], np.arange(8.0))
    mgr.close()


def test_replicas_rotate_and_rewind_with_disk(tmp_path):
    reps = PeerReplicaStore()
    mgr = CheckpointManager(tmp_path, async_save=False, max_to_keep=2,
                            replicas=reps)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"s": s})
    assert mgr.steps() == [3, 4]
    assert reps.steps() == [3, 4]            # memory tier rotates too
    assert 4 in mgr.discard_from(4)
    assert reps.steps() == [3]               # and rewinds with the disk
    mgr.close()


def test_quarantine_gc_reaps_oldest_first(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=False, max_to_keep=None,
                            keep_quarantined=2)
    for s in (1, 2, 3, 4):
        (tmp_path / f".quarantine_step_{s:08d}").mkdir()
    k0 = tm.counter_value("checkpoint.quarantine_reaps")
    mgr.save(9, {"x": 1})
    left = sorted(p.name for p in tmp_path.iterdir()
                  if p.name.startswith(".quarantine"))
    assert left == [".quarantine_step_00000003",
                    ".quarantine_step_00000004"]
    assert tm.counter_value("checkpoint.quarantine_reaps") == k0 + 2
    mgr.close()


def test_keep_quarantined_validation(tmp_path):
    with pytest.raises(ValueError, match="keep_quarantined"):
        CheckpointManager(tmp_path, keep_quarantined=-1)


# ---------------------------------------------------------------------------
# recovery: the partition verdict
# ---------------------------------------------------------------------------


def test_classify_partition_by_type_and_text():
    spec = faults.FaultSpec.from_dict(
        {"site": "x", "action": "partition", "groups": _SPLIT}, 0)
    assert recovery.classify(faults.InjectedPartition(spec, {})) == \
        "partition"
    assert recovery.classify(
        RuntimeError("network partition detected")) == "partition"


def test_quorum_side_restores_and_retries(tmp_path):
    domains.configure(_SPLIT)
    faults.configure(seed=9, plan=[
        {"site": "train.step", "match": {"step": 3}, "action": "partition",
         "at": 1, "groups": _SPLIT, "observer": 0}])
    r0 = tm.counter_value("recovery.retries", verdict="partition")
    k0 = tm.counter_value("elastic.shrinks")
    with Trainer(mlp_task(batch_size=56), ckpt_dir=tmp_path, save_every=2,
                 policy=_fast_policy(), peer_replicas=True) as t:
        res = t.fit(5)
    assert len(res["losses"]) == 5
    assert tm.counter_value("recovery.retries",
                            verdict="partition") == r0 + 1
    assert tm.counter_value("elastic.shrinks") == k0 + 1
    assert elastic.manager().live_ranks() == [0, 1, 2, 3, 4]


def test_minority_side_exits_typed_with_one_bundle(tmp_path):
    domains.configure(_SPLIT)
    faults.configure(seed=9, plan=[
        {"site": "train.step", "match": {"step": 3}, "action": "partition",
         "at": 1, "groups": _SPLIT, "observer": 6}])
    b0 = flight.crash_bundle_count()
    r0 = tm.counter_value("recovery.retries", verdict="partition")
    x0 = tm.counter_value("recovery.minority_exits")
    with Trainer(mlp_task(batch_size=56), ckpt_dir=tmp_path, save_every=2,
                 policy=_fast_policy(), peer_replicas=True) as t:
        with pytest.raises(recovery.MinorityPartitionExit) as ei:
            t.fit(5)
    assert ei.value.side == [5, 6, 7]
    assert ei.value.lost == [0, 1, 2, 3, 4]
    # exactly ONE classified flight bundle, and the step never retried
    assert flight.crash_bundle_count() - b0 == 1
    assert tm.counter_value("recovery.retries", verdict="partition") == r0
    assert tm.counter_value("recovery.minority_exits") == x0 + 1


def test_minority_exit_passes_through_nested_recovery():
    exc = recovery.MinorityPartitionExit("gone", side=[5], lost=[0])
    b0 = flight.crash_bundle_count()
    with pytest.raises(recovery.MinorityPartitionExit):
        recovery.run_with_recovery(
            lambda: (_ for _ in ()).throw(exc), policy=_fast_policy())
    assert flight.crash_bundle_count() == b0     # no second bundle


# ---------------------------------------------------------------------------
# serve: minority-side typed drain
# ---------------------------------------------------------------------------


def test_minority_server_drains_typed():
    domains.configure(_SPLIT)
    faults.configure(seed=1, plan=[
        {"site": "train.step", "action": "partition", "at": 1,
         "groups": _SPLIT, "observer": 6}])
    with pytest.raises(faults.InjectedPartition):
        faults.check("train.step")
    m = elastic.manager()
    m.probe()                                # caches the minority verdict
    assert m.partition_verdict()["verdict"] == "minority"
    s0 = tm.counter_value("serve.partition_drains")
    srv = serve.Server(serve.ServeConfig(workers=1),
                       policy=_fast_policy())
    srv.register("echo", lambda ps: list(ps))
    try:
        with pytest.raises(Draining):
            srv.submit("echo", 1.0)
        assert tm.counter_value("serve.partition_drains") == s0 + 1
        # drained, not wedged: a second submit stays typed
        with pytest.raises(Draining):
            srv.submit("echo", 2.0)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# the partition acceptance soak
# ---------------------------------------------------------------------------

_PARTITION_PLAN = [
    {"site": "train.step", "match": {"step": 5}, "action": "partition",
     "at": 1, "groups": _SPLIT, "observer": 0},
]


def _soak(tmp_path, plan, seed, steps=8, **kw):
    faults.clear()
    elastic.manager().reset()
    domains.configure(_SPLIT)
    if plan is not None:
        faults.configure(plan=plan, seed=seed)
    kw.setdefault("policy", _fast_policy())
    t = Trainer(mlp_task(batch_size=56), ckpt_dir=tmp_path, save_every=2,
                **kw)
    try:
        return t.fit(steps), elastic.manager().live_ranks()
    finally:
        t.close()


@pytest.mark.slow
def test_partition_soak_quorum_side_peer_restore_zero_disk_reads(tmp_path):
    """The acceptance soak: a seeded partition splits the 8-rank mesh
    5/3 at step 5.  The quorum side must shrink to its surviving
    domains, restore every shard from PEER replicas with zero disk
    reads (restore-source counter witness), and finish with a
    post-resume loss trajectory bit-identical to a fault-free run
    restarted from the same step on the same survivors."""
    b0 = flight.crash_bundle_count()
    r0 = tm.counter_value("recovery.retries", verdict="partition")
    dr_before_total = tm.counter_value("checkpoint.disk_reads")
    p0 = tm.counter_value("checkpoint.restore_source", source="peer")
    d0 = tm.counter_value("checkpoint.restore_source", source="disk")

    res, survivors = _soak(tmp_path / "chaos", _PARTITION_PLAN, seed=42,
                           peer_replicas=True)

    # quorum side completed on its own domains
    assert survivors == [0, 1, 2, 3, 4]
    assert len(res["losses"]) == 8
    assert flight.crash_bundle_count() - b0 == 1
    assert tm.counter_value("recovery.retries",
                            verdict="partition") == r0 + 1
    # the restore was served ENTIRELY by the peer-replica tier
    assert tm.counter_value("checkpoint.restore_source",
                            source="peer") == p0 + 1
    assert tm.counter_value("checkpoint.restore_source",
                            source="disk") == d0
    assert tm.counter_value("checkpoint.disk_reads") == dr_before_total

    # comparison: a fault-free run restarted from the same step (4) on
    # the same survivors, from the same on-disk history
    faults.clear()
    import os
    import shutil
    src, dst = tmp_path / "chaos", tmp_path / "clean"
    shutil.copytree(src, dst,
                    ignore=shutil.ignore_patterns(".quarantine*"))
    for p in sorted(os.listdir(dst)):
        if p.startswith("step_") and int(p[5:]) > 4:
            shutil.rmtree(dst / p)
    domains.configure(_SPLIT)
    with Trainer(mlp_task(batch_size=56), ckpt_dir=dst, save_every=1000,
                 policy=_fast_policy(), ranks=survivors) as t2:
        res2 = t2.fit(8)
    assert res2["start"] == 4
    assert res2["losses"] == res["losses"][4:]   # bitwise equality

    # leak gate: registry and HBM ledger drain (conftest re-asserts)
    assert dat.live_ids() == []
    assert tmem.live_bytes() == 0


@pytest.mark.slow
def test_partition_soak_minority_exits_clean_with_one_bundle(tmp_path):
    plan = [dict(_PARTITION_PLAN[0], observer=6)]
    b0 = flight.crash_bundle_count()
    faults.clear()
    elastic.manager().reset()
    domains.configure(_SPLIT)
    faults.configure(plan=plan, seed=42)
    with Trainer(mlp_task(batch_size=56), ckpt_dir=tmp_path / "m",
                 save_every=2, policy=_fast_policy(),
                 peer_replicas=True) as t:
        with pytest.raises(recovery.MinorityPartitionExit):
            t.fit(8)
    assert flight.crash_bundle_count() - b0 == 1
    assert dat.live_ids() == []
    assert tmem.live_bytes() == 0


@pytest.mark.slow
def test_partition_soak_replay_is_deterministic(tmp_path):
    def _normalized_history():
        out = []
        for f in faults.history():
            f = dict(f, labels={k: v for k, v in f["labels"].items()
                                if k != "path"})
            out.append(f)
        return out

    res1, _ = _soak(tmp_path / "a", _PARTITION_PLAN, seed=42,
                    peer_replicas=True)
    h1 = _normalized_history()
    res2, _ = _soak(tmp_path / "b", _PARTITION_PLAN, seed=42,
                    peer_replicas=True)
    h2 = _normalized_history()
    assert res1["losses"] == res2["losses"]
    assert h1 == h2
