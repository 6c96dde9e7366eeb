"""Protocol model checker + declarative ring-schedule tests.

The checker (analysis/protocol.py) must prove all six shipped RDMA
ring-kernel schedules clean over every rank-asynchronous interleaving
(semaphore drain, no in-flight slot races, write-once discipline, no
starvation, token-exact data flow) AND refute every seeded mutant with
a printed interleaving counterexample — the mutation harness is the
proof that the gate gates.  Unit halves: hand-built miniature schedules
trigger each violation kind individually, so a checker regression is
attributable to one property.
"""

import subprocess
import sys
from pathlib import Path

import pytest

from distributedarrays_tpu.analysis import protocol
from distributedarrays_tpu.ops import ring_schedules as rs

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# the shipped schedules verify
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", protocol.KERNEL_NAMES)
@pytest.mark.parametrize("p", [2, 3, 4])
def test_shipped_schedules_verify(name, p):
    for nc in ((1, 2) if name in ("ring_all_to_all",
                                  "ring_reduce_scatter") else (1,)):
        res = protocol.check_schedule(rs.build(name, p, nc))
        assert res.ok, f"{name} p={p} nc={nc}: {res.kind}: {res.detail}"
        assert res.states >= 1


def test_schedules_cover_all_six_kernels():
    # the emitter and the checker share ops/ring_schedules.SCHEDULES as
    # their single source of truth — every shipped kernel is registered
    assert set(protocol.KERNEL_NAMES) == {
        "ring_all_gather", "ring_all_to_all", "ring_reduce_scatter",
        "ring_allgather_matmul", "ring_allgather_matmul_rhs",
        "ring_matmul_reducescatter"}


def test_schedules_are_pure_data():
    # hashable, comparable, deterministic — the mutation harness diffs
    # programs and the lru caches key on (p, nc)
    a = rs.build("ring_reduce_scatter", 4, 2)
    b = rs.build("ring_reduce_scatter", 4, 2)
    assert a == b and hash(a.program) == hash(b.program)
    assert a != rs.build("ring_reduce_scatter", 4, 1)


# ---------------------------------------------------------------------------
# the all-to-all's send schedule: every destination's link kept busy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nc", [1, 2, 4])
@pytest.mark.parametrize("p", [2, 3, 4, 5, 8])
def test_all_to_all_keeps_every_destination_in_flight(p, nc):
    # read off the program as a rank issues it, a DMA counted in flight
    # from its Start to the WaitSend on its slot (the latest it can be)
    sched = rs.all_to_all_schedule(p, nc)
    dests, depth = rs.a2a_window(p, nc)
    assert (dests, depth) == (p - 1, min(nc, 2))
    assert sched.sem_slots()["send"] == dests * depth
    for me in range(p):
        env = {"me": me, "mod": lambda a, n: a % n}
        others = set(range(p)) - {me}
        busy = {}                   # send slot -> destination in flight
        owner = {}                  # send slot -> its one destination
        issued = []                 # (destination, chunk), issue order
        waited_send = waited_local = False
        for ins in sched.program:
            d = ins.dma
            if d.peer is None:
                if isinstance(ins, rs.Start):
                    assert not issued       # the local copy starts first
                else:
                    # ... and is waited under the wire: after the first
                    # remote start, and in fact after the sends' drain
                    assert issued and not busy
                    waited_local = True
                continue
            if isinstance(ins, rs.Start):
                dst = rs.ev(d.peer, env)
                assert d.send not in busy, "slot restarted before its wait"
                assert owner.setdefault(d.send, dst) == dst
                busy[d.send] = dst
                issued.append((dst, rs.ev(d.src[1], env)[1]))
            elif isinstance(ins, rs.WaitSend):
                if not waited_send:
                    # no wait before every destination has been started
                    assert {dst for dst, _ in issued} == others
                    waited_send = True
                del busy[d.send]            # KeyError: waited an idle slot
            left = {dst for dst in others
                    if sum(1 for x, _ in issued if x == dst) < nc}
            if len(issued) >= dests and len(issued) < dests * nc:
                # between the first round and the last start, whoever has
                # pieces left to send has one in flight
                assert left <= set(busy.values())
        assert waited_local and not busy
        # chunk-major, destination-minor: chunk c to everyone before c+1
        assert [c for _, c in issued] == sorted(c for _, c in issued)
        assert len(set(issued)) == len(issued) == dests * nc
        # every piece still lands once, at the offset it always had
        final = {(rs.ev(k, env), rs.ev(t, env))
                 for (_b, k), t in sched.final}
        want = {((me, "all"), ("piece", me, me, "all"))} | {
            ((s, c), ("piece", s, me, c)) for s in others
            for c in range(nc)}
        assert final == want


@pytest.mark.parametrize("p", [2, 3, 4, 5, 8])
def test_all_to_all_round_names_the_same_route_on_every_rank(p):
    # where p is a power of two the ranks pair up (me ^ off): whom I send
    # to in a round's j-th place sends to me in its own j-th place;
    # elsewhere the j-th place is me + off round the ring
    offs = rs.a2a_offsets(p)
    assert sorted(offs) == list(range(1, p))
    sched = rs.all_to_all_schedule(p, 1)

    def round_of(me):
        env = {"me": me, "mod": lambda a, n: a % n}
        return [rs.ev(i.dma.peer, env) for i in sched.program
                if isinstance(i, rs.Start) and i.dma.peer is not None]

    for me in range(p):
        dests = round_of(me)
        if p & (p - 1) == 0:
            assert dests == [me ^ off for off in offs]
            assert all(round_of(d)[j] == me for j, d in enumerate(dests))
        else:
            assert dests == [(me + off) % p for off in offs]


# ---------------------------------------------------------------------------
# the mutation harness: every mutant refuted, with a counterexample
# ---------------------------------------------------------------------------


def test_verify_protocols_end_to_end():
    rep = protocol.verify_protocols(ps=(2, 3, 4), depths=(1, 2))
    assert rep["ok"]
    assert all(r.ok for r in rep["kernels"])
    assert rep["mutants"], "mutation harness produced no mutants"
    for m in rep["mutants"]:
        assert not m.ok, f"MISSED mutant {m.name}"
        assert m.kind != "state-budget"
        assert m.counterexample, "refutation must carry an interleaving"
        assert m.mutation in protocol.MUTATIONS


def test_every_credit_kernel_has_a_credit_mutant():
    # the credit-gated kernels must each be refutable by dropping one
    # credit take — the exact bug class the credits exist for
    rep = protocol.verify_protocols(ps=(2,), depths=(1,), mutant_p=4)
    got = {m.name.split("!")[0] for m in rep["mutants"]
           if m.mutation == "drop-credit-take"}
    assert got == {"ring_reduce_scatter", "ring_allgather_matmul",
                   "ring_allgather_matmul_rhs",
                   "ring_matmul_reducescatter"}


def test_mutant_counterexample_is_a_readable_interleaving():
    sched = rs.build("ring_allgather_matmul", 4, 1)
    m = protocol.mutate(sched, "drop-credit-take")
    res = protocol.check_schedule(m)
    assert not res.ok
    trace = "\n".join(res.counterexample)
    # the trace names ranks, DMA starts and landings — a reviewer can
    # replay it against docs/pallas_collectives.md's schedule diagrams
    assert "start dma" in trace and "landed" in trace
    assert res.kind in ("race", "stale-read")


def test_mutate_returns_none_when_not_applicable():
    # the all-gather has no credits to drop
    assert protocol.mutate(rs.build("ring_all_gather", 4),
                           "drop-credit-take") is None
    with pytest.raises(ValueError):
        protocol.mutate(rs.build("ring_all_gather", 4), "no-such")


def test_format_report_prints_verdicts_and_skips():
    rep = protocol.verify_protocols(ps=(2, 8), depths=(1,),
                                    mutants=False)
    text = protocol.format_report(rep)
    assert "OK " in text and "protocol verification: OK" in text
    # p=8 exceeds most kernels' tractable caps: skips are PRINTED,
    # never silent
    assert rep["skipped"] and "SKIP" in text
    assert "SKIP ring_all_to_all" not in text
    # the all-to-all reduces to one canonical interleaving -> checked
    a2a = [r for r in rep["kernels"]
           if r.name == "ring_all_to_all" and r.p == 8]
    assert a2a and a2a[0].ok


def test_raised_max_states_lifts_the_tractability_cap(monkeypatch):
    # the SKIP line advertises a deep-run command with a raised
    # --max-states; that command must actually RUN the skipped combo,
    # not skip it again.  Pin the all-to-all's cap low (it is the one
    # kernel cheap at any p) and check both sides of the default budget.
    monkeypatch.setitem(protocol.P_CAPS, "ring_all_to_all", 2)
    kw = dict(ps=(4,), depths=(1,), mutants=False)
    skipped_default = protocol.verify_protocols(**kw)
    assert any(n == "ring_all_to_all"
               for n, _, _ in skipped_default["skipped"])
    deep = protocol.verify_protocols(
        **kw, max_states=protocol.DEFAULT_MAX_STATES + 1)
    assert not any(n == "ring_all_to_all" for n, _, _ in deep["skipped"])
    ran = [r for r in deep["kernels"] if r.name == "ring_all_to_all"]
    assert ran and ran[0].ok


# ---------------------------------------------------------------------------
# unit violations on miniature hand-built schedules
# ---------------------------------------------------------------------------


def _mini(program, *, sems=(("s", 0),), final=(),
          buffers=(("b", rs.BufferSpec("scratch")),), p=2):
    return rs.Schedule("mini", p, (), buffers, sems, tuple(program),
                       tuple(final))


def test_violation_drain():
    # a local copy whose semaphore is never waited: +1 at exit
    d = rs.Dma(src=("b", (0,)), dst=("b", (1,)), sem=("s", 0), token=1)
    res = protocol.check_schedule(_mini([rs.Start(d)]))
    assert not res.ok and res.kind == "drain"
    assert "undrained" in res.detail


def test_violation_starvation():
    # a wait with no signal anywhere: deadlock, reported not hung
    d = rs.Dma(src=("b", (0,)), dst=("b", (1,)), sem=("s", 0))
    res = protocol.check_schedule(_mini([rs.WaitLocal(d)]))
    assert not res.ok and res.kind == "starvation"
    assert "deadlock" in res.detail


def test_violation_write_once():
    d1 = rs.Dma(src=("b", (0,)), dst=("o", (0,)), sem=("s", 0), token=1)
    res = protocol.check_schedule(_mini(
        [rs.Start(d1), rs.WaitLocal(d1), rs.Start(d1), rs.WaitLocal(d1)],
        buffers=(("b", rs.BufferSpec("scratch")),
                 ("o", rs.BufferSpec("output", write_once=True)))))
    assert not res.ok and res.kind == "write-once"


def test_violation_race_write_while_in_flight():
    # second copy writes b[1] while the first is still landing into it
    d1 = rs.Dma(src=("b", (0,)), dst=("b", (1,)), sem=("s", 0), token=1)
    d2 = rs.Dma(src=("b", (2,)), dst=("b", (1,)), sem=("s", 0), token=2)
    res = protocol.check_schedule(_mini(
        [rs.Start(d1), rs.Start(d2), rs.WaitLocal(d1),
         rs.WaitLocal(d2)]))
    assert not res.ok and res.kind == "race"


def test_violation_stale_read_token():
    # a compute expecting a token the slot never received
    c = rs.Compute("use", reads=((("b", (0,)), ("fresh",)),))
    res = protocol.check_schedule(_mini([c]))
    assert not res.ok and res.kind == "stale-read"
    assert "<unwritten>" in res.detail


def test_violation_final_token():
    res = protocol.check_schedule(_mini(
        [], final=(((("b", (0,))), ("never",)),)))
    assert not res.ok and res.kind == "final"


def test_state_budget_is_a_failure_not_a_pass():
    res = protocol.check_schedule(rs.build("ring_reduce_scatter", 4, 2),
                                  max_states=3)
    assert not res.ok and res.kind == "state-budget"
    # and a budgeted-out mutant does NOT count as caught
    rep = {"ok": None, "kernels": [], "mutants": [res]}
    assert "MISSED" in protocol.format_report(rep)


def test_per_link_fifo_is_modeled():
    """Same-link DMA landings are delivered in issue order (ICI
    in-order delivery) — the 2-revolving-slot all-gather is only
    correct under that premise, so the premise must be explicit: an
    out-of-order model would (and, before the FIFO constraint, did)
    refute ring_all_gather at p >= 4."""
    res = protocol.check_schedule(rs.build("ring_all_gather", 4))
    assert res.ok
    # the premise is documented where reviewers will look
    assert "in-order" in protocol.__doc__


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_verify_protocols_roundtrip():
    r = subprocess.run(
        [sys.executable, "-m", "distributedarrays_tpu.analysis",
         "verify-protocols", "--ps", "2,3", "--depths", "1", "--quiet"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "protocol verification: OK" in r.stdout
    assert "CAUGHT" in r.stdout          # mutants ran and were refuted


def test_cli_verify_protocols_fails_closed_on_budget():
    r = subprocess.run(
        [sys.executable, "-m", "distributedarrays_tpu.analysis",
         "verify-protocols", "--ps", "4", "--depths", "2",
         "--max-states", "5", "--no-mutants", "--quiet"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert r.returncode == 1
    assert "state-budget" in r.stdout or "FAILED" in r.stdout


# ---------------------------------------------------------------------------
# mesh-axis variants (PR 19: per-axis sub-rings on 2-D/3-D meshes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mesh_shape,axis", list(protocol.DEFAULT_MESHES))
def test_mesh_schedule_ring_all_gather_verifies(mesh_shape, axis):
    p = mesh_shape[axis]
    sched = rs.build("ring_all_gather", p, 2)
    res = protocol.check_mesh_schedule(sched, mesh_shape, axis)
    assert res.ok, (mesh_shape, axis, res.kind, res.detail)


def test_verify_mesh_protocols_end_to_end():
    # every shipped schedule x every (mesh, axis) variant verifies, and
    # every mesh-geometry mutant is REFUTED (not budget-skipped)
    rep = protocol.verify_mesh_protocols()
    assert rep["ok"]
    assert all(r.ok for r in rep["kernels"])
    assert len(rep["kernels"]) >= len(protocol.KERNEL_NAMES) * \
        len(protocol.DEFAULT_MESHES)
    assert rep["mutants"], "mesh mutant harness must run"
    for m in rep["mutants"]:
        assert not m.ok and m.kind != "state-budget", m.name
        assert m.mutation in protocol.MESH_MUTATIONS


@pytest.mark.parametrize("mutation", protocol.MESH_MUTATIONS)
def test_mesh_mutant_addr_leaves_the_subring(mutation):
    # the mutant address computations really do land outside the armed
    # sub-ring for some (rank, pos) — the property the isolation check
    # refutes them by
    mesh_shape, axis = (2, 4), 1
    addr = protocol.mesh_mutant_addr(mesh_shape, axis, mutation)
    escaped = False
    for ring in rs.mesh_subrings(mesh_shape, axis):
        for rank in ring:
            for pos in range(len(ring)):
                if addr(rank, pos) not in ring:
                    escaped = True
    assert escaped


def test_cli_verify_protocols_mesh_flag():
    r = subprocess.run(
        [sys.executable, "-m", "distributedarrays_tpu.analysis",
         "verify-protocols", "--ps", "2", "--depths", "1", "--mesh",
         "--quiet"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "protocol verification: OK" in r.stdout
