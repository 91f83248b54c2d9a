"""Elastic membership: epoch state machine + elastic-runner acceptance
(DESIGN.md §13).

Two layers.  The unit layer pins the ClusterMembership state machine
(epoch bumps, spare pool, scheduled joins, monitor coupling) and the
HeartbeatMonitor's stall credit.  The acceptance layer is the elastic
twin of tests/test_cluster.py's invariant: a run where a member DIES and
a spare replaces it, or a scheduled joiner enters mid-run, must stay
bit-identical to train_reference replaying the observed responder trace
on the spare-extended config — elasticity changes who computes, never
what is computed.
"""
import jax
import numpy as np
import pytest

from repro.cluster import (
    ClusterMembership,
    ClusterRunner,
    DeadWorkerLatency,
    DeterministicLatency,
    LognormalTailLatency,
)
from repro.core import protocol
from repro.data import synthetic
from repro.obs.trace import Recorder
from repro.runtime.resilience import HeartbeatMonitor


@pytest.fixture(scope="module")
def binary_data():
    return synthetic.mnist_like(jax.random.PRNGKey(42), m=300, d=24)


# ---------------------------------------------------------------------------
# ClusterMembership state machine
# ---------------------------------------------------------------------------

def test_view_is_an_immutable_epoch_snapshot():
    ms = ClusterMembership(range(4), spares=[4, 5])
    v0 = ms.view()
    assert v0.epoch == 0
    assert v0.members == (0, 1, 2, 3)
    assert 2 in v0 and 4 not in v0 and len(v0) == 4
    ms.admit(4, round=3)
    # the old snapshot is untouched — the epoch fence contract
    assert v0.epoch == 0 and v0.members == (0, 1, 2, 3)
    v1 = ms.view()
    assert v1.epoch == 1 and v1.members == (0, 1, 2, 3, 4)


def test_spares_must_be_disjoint_from_members():
    with pytest.raises(AssertionError):
        ClusterMembership(range(4), spares=[3])


def test_schedule_join_is_idempotent_and_due_at_fence():
    ms = ClusterMembership(range(3), spares=[3, 4])
    ms.schedule_join(3, at_round=5)
    ms.schedule_join(3, at_round=9)          # duplicate request: ignored
    ms.schedule_join(1, at_round=0)          # already a member: ignored
    ms.schedule_join(4, at_round=2)
    assert ms.due_joins(1) == []
    assert ms.due_joins(2) == [4]
    assert ms.due_joins(7) == [3, 4]         # request order, both due
    ms.admit(4, round=2)
    assert ms.due_joins(7) == [3]            # admission clears the request


def test_take_spare_pops_lowest_until_dry():
    ms = ClusterMembership(range(2), spares=[5, 3])
    assert ms.spares == (3, 5)
    assert ms.take_spare() == 3
    assert ms.take_spare() == 5
    assert ms.take_spare() is None


def test_admit_and_leave_bump_epoch_and_drive_monitor():
    mon = HeartbeatMonitor(3, timeout_s=10.0, now=0.0)
    ms = ClusterMembership(range(3), monitor=mon, spares=[3])
    v = ms.admit(3, round=4, now=7.0)
    assert v.epoch == 1 and 3 in v
    assert ms.spares == ()
    assert 3 in mon.workers                  # monitor tracks the joiner
    assert mon.workers[3].last_heartbeat == 7.0
    v = ms.leave(1, round=6, now=9.0)
    assert v.epoch == 2 and 1 not in v
    assert 1 not in mon.workers              # retired slot untracked
    assert ms.departed == frozenset({1})
    # a heartbeat from the retired slot is liveness evidence for nobody
    mon.heartbeat(1, now=9.5)
    assert 1 not in mon.workers


def test_leave_then_spare_replacement_sequence():
    ms = ClusterMembership(range(4), spares=[4])
    ms.leave(2, round=3, now=1.0)
    spare = ms.take_spare()
    assert spare == 4
    v = ms.admit(spare, round=3, now=1.0)
    assert v.epoch == 2
    assert v.members == (0, 1, 3, 4)
    kinds = [(tr.kind, tr.worker, tr.epoch) for tr in ms.transitions]
    assert kinds == [("leave", 2, 1), ("join", 4, 2)]
    assert all(tr.round == 3 for tr in ms.transitions)


def test_double_admit_and_unknown_leave_are_caller_bugs():
    ms = ClusterMembership(range(2), spares=[2])
    ms.admit(2, round=0)
    with pytest.raises(AssertionError):
        ms.admit(2, round=1)
    with pytest.raises(AssertionError):
        ms.leave(7, round=1)


def test_departed_slot_may_rejoin_after_resilient_restore():
    ms = ClusterMembership(range(3))
    ms.leave(0, round=2, now=0.0)
    ms.schedule_join(0, at_round=5)
    assert ms.due_joins(5) == [0]
    v = ms.admit(0, round=5, now=3.0)
    assert 0 in v and ms.departed == frozenset()


# ---------------------------------------------------------------------------
# HeartbeatMonitor stall credit
# ---------------------------------------------------------------------------

def test_credit_stall_keeps_live_fleet_alive_through_barrier():
    """A master-side barrier (joiner provisioning, respawn) suspends the
    per-round acks that are the detector's only heartbeat source: credit
    shifts every previously-live worker past the silent window."""
    mon = HeartbeatMonitor(3, timeout_s=2.0, now=0.0)
    mon.heartbeat(0, now=1.0)
    mon.heartbeat(1, now=1.0)
    # a 5-second admission barrier: without credit everyone looks dead
    assert mon.is_dead(0, now=6.0)
    mon.credit_stall(5.0, now=6.0)
    assert not mon.is_dead(0, now=6.0)
    assert not mon.is_dead(1, now=6.0)
    assert mon.workers[0].last_heartbeat == pytest.approx(6.0)


def test_credit_stall_does_not_resurrect_the_already_dead():
    """A worker whose silence predates the stall was dead on its own
    merits — the credit must not mask a real failure."""
    mon = HeartbeatMonitor(2, timeout_s=1.0, now=0.0)
    mon.heartbeat(0, now=10.0)
    # worker 1 last heartbeated at 0.0: already past the timeout when the
    # stall began at t=10
    mon.credit_stall(3.0, now=13.0)
    assert not mon.is_dead(0, now=13.0)
    assert mon.is_dead(1, now=13.0)
    assert mon.workers[1].last_heartbeat == 0.0


def test_credit_stall_never_stamps_the_future():
    mon = HeartbeatMonitor(1, timeout_s=5.0, now=0.0)
    mon.heartbeat(0, now=4.0)
    mon.credit_stall(3.0, now=5.0)           # 4 + 3 would be t=7 > now
    assert mon.workers[0].last_heartbeat == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# Elastic ClusterRunner acceptance: bit-identity through transitions
# ---------------------------------------------------------------------------

def test_elastic_leave_with_spare_replacement_bit_identical(binary_data):
    """A member dies mid-run; the failure detector retires it at a round
    fence and the pre-provisioned spare slot is admitted as its permanent
    replacement.  The weights must equal train_reference on the
    spare-EXTENDED config replaying the observed trace — the consecutive
    evaluation points make shares 0..N-1 and every decode over them
    bit-identical to the fixed-N scheme."""
    x, y = binary_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)        # threshold 7
    lat = DeadWorkerLatency(DeterministicLatency(base=1.0, skew=0.1),
                            deaths={2: 3})
    recorder = Recorder()
    runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y, lat,
                           heartbeat_timeout_s=4.0, round_timeout_s=60.0,
                           spares=1, recorder=recorder)
    assert runner.cfg.N == 9                 # extended; threshold unchanged
    assert runner.cfg.threshold == cfg.threshold
    w = runner.run(16)

    ms = runner.membership
    kinds = [(tr.kind, tr.worker) for tr in ms.transitions]
    assert ("leave", 2) in kinds and ("join", 8) in kinds
    assert ms.epoch == 2 and ms.spares == ()
    assert 2 not in ms.view() and 8 in ms.view()
    # after the transition round the retired slot is NEVER dispatched again
    # and the spare slot answers in its place
    fence = max(tr.round for tr in ms.transitions)
    for t, rec in runner.records.items():
        if t >= fence:
            assert 2 not in set(map(int, rec.dispatched))
            assert 8 in set(map(int, rec.dispatched))
    stats = runner.wait_stats()
    assert stats["membership"]["leaves"] == 1.0
    assert stats["membership"]["joins"] == 1.0
    # the transitions are on the flight recorder's trace, at their fence
    spans = recorder.find("membership_transition")
    assert spans and {int(s.args["round"]) for s in spans} == {
        tr.round for tr in ms.transitions}

    w_ref, _ = protocol.train_reference(runner.cfg, jax.random.PRNGKey(7),
                                        x, y, iters=16,
                                        survivor_fn=runner.survivor_fn())
    assert (np.asarray(w) == np.asarray(w_ref)).all()


def test_elastic_scheduled_join_bit_identical(binary_data):
    """A joiner scheduled for round 3 (the sim twin of a late worker's
    Join frame): rounds before the fence run on the base fleet, rounds
    after include the spare slot — all bit-identical to the reference."""
    x, y = binary_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    lat = LognormalTailLatency(seed=3, tail_prob=0.3, tail_scale=25.0)
    runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y, lat,
                           spares=1, join_schedule={8: 3})
    w = runner.run(12)

    ms = runner.membership
    assert ms.epoch == 1
    assert [(tr.kind, tr.worker, tr.round) for tr in ms.transitions] == [
        ("join", 8, 3)]
    for t, rec in runner.records.items():
        assert (8 in set(map(int, rec.dispatched))) == (t >= 3)
    w_ref, _ = protocol.train_reference(runner.cfg, jax.random.PRNGKey(7),
                                        x, y, iters=12,
                                        survivor_fn=runner.survivor_fn())
    assert (np.asarray(w) == np.asarray(w_ref)).all()


def test_non_elastic_runner_is_bit_identical_to_fixed_fleet(binary_data):
    """spares=0 and no join schedule keep the historical fixed-fleet
    behavior EXACTLY: epoch parked at 0, no transitions, same weights as a
    pre-elastic run (the reference on the unextended config)."""
    x, y = binary_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    lat = LognormalTailLatency(seed=3, tail_prob=0.3, tail_scale=25.0)
    runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y, lat)
    w = runner.run(12)
    assert not runner.elastic
    assert runner.membership.epoch == 0
    assert runner.membership.transitions == []
    assert runner.cfg.N == 8
    w_ref, _ = protocol.train_reference(cfg, jax.random.PRNGKey(7), x, y,
                                        iters=12,
                                        survivor_fn=runner.survivor_fn())
    assert (np.asarray(w) == np.asarray(w_ref)).all()


def test_spare_extension_leaves_base_shares_bit_identical():
    """The coding-scheme fact elasticity rests on: CodingScheme points are
    consecutive, so the N+spares encode matrix's first N columns — hence
    shares 0..N-1 — equal the fixed-N scheme's exactly."""
    import dataclasses
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    ext = dataclasses.replace(cfg, N=9)
    u = np.asarray(cfg.scheme.encode_matrix)
    u_ext = np.asarray(ext.scheme.encode_matrix)
    assert u_ext.shape[1] == u.shape[1] + 1
    assert (u_ext[:, : u.shape[1]] == u).all()
