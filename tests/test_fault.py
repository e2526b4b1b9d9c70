"""Fault-tolerance runtime: retry policy, call-level watchdog, replica
health, fault-injection primitives (FaultPlan / ShardLostError)."""
import time

import pytest

from repro.runtime.fault import (
    FaultPlan,
    FaultSpec,
    ReplicaHealth,
    ReplicaLostError,
    RetryPolicy,
    ShardLostError,
)


def test_retry_policy_delays_reiterable_and_exponential():
    p = RetryPolicy(max_retries=3, backoff_s=1.0, backoff_mult=2.0)
    d1 = p.delays()
    assert d1 == [1.0, 2.0, 4.0]
    assert list(d1) == list(d1)      # materialized: safe to iterate twice
    assert p.delays() == d1          # and fresh per call


def test_retry_policy_jitter_bounds():
    p = RetryPolicy(max_retries=4, backoff_s=0.5, backoff_mult=3.0, jitter=0.5)
    base = RetryPolicy(max_retries=4, backoff_s=0.5, backoff_mult=3.0).delays()
    d = p.delays(seed=0)
    for got, b in zip(d, base):
        assert b <= got <= b * 1.5   # scaled by 1 + U(0, jitter)
    assert p.delays(seed=1) != p.delays(seed=2)


def test_with_timeout_passthrough_and_timeout():
    from repro.runtime.fault import with_timeout

    assert with_timeout(lambda a, b: a + b, None, 1, b=2) == 3
    assert with_timeout(lambda: "ok", 5.0) == "ok"

    import time as _time
    with pytest.raises(TimeoutError, match="exceeded"):
        with_timeout(_time.sleep, 0.02, 1.0)


def test_with_timeout_propagates_exceptions():
    from repro.runtime.fault import with_timeout

    def boom():
        raise ValueError("inner failure")

    with pytest.raises(ValueError, match="inner failure"):
        with_timeout(boom, 5.0)


def test_shard_lost_error_carries_shard():
    e = ShardLostError(3)
    assert e.shard == 3 and "shard 3" in str(e)
    assert isinstance(e, RuntimeError)
    assert ShardLostError(1, "custom").args == ("custom",)


def test_fault_plan_fires_once_at_its_dispatch():
    plan = FaultPlan([FaultSpec("shard_error", shard=2, at_dispatch=1)])
    plan.on_dispatch()                       # dispatch 0: armed, silent
    with pytest.raises(ShardLostError) as ei:
        plan.on_dispatch()                   # dispatch 1: fires
    assert ei.value.shard == 2
    plan.on_dispatch()                       # spent: at most once
    assert plan.dispatches == 3
    assert len(plan.fired) == 1


def test_fault_plan_wedge_sleeps_and_kind_validated():
    plan = FaultPlan([FaultSpec("wedge", at_dispatch=0, wedge_s=0.02)])
    t0 = time.monotonic()
    plan.on_dispatch()
    assert time.monotonic() - t0 >= 0.02
    assert plan.fired
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("meteor_strike")


def test_replica_lost_error_carries_replica():
    e = ReplicaLostError(1)
    assert e.replica == 1 and "replica 1" in str(e)
    assert isinstance(e, RuntimeError)


def test_fault_plan_replica_kind_arms_then_fires_on_target_replica():
    """Replica kinds ARM at at_dispatch and fire on the first armed
    dispatch routed to the target replica — routing is load-dependent, so
    unlike shard kinds they cannot be pinned to an exact dispatch index."""
    plan = FaultPlan([FaultSpec("replica_error", replica=1, at_dispatch=2)])
    plan.on_dispatch(replica=1)              # dispatch 0: not armed yet
    plan.on_dispatch(replica=1)              # dispatch 1: not armed yet
    plan.on_dispatch(replica=0)              # dispatch 2: armed, wrong target
    with pytest.raises(ReplicaLostError) as ei:
        plan.on_dispatch(replica=1)          # dispatch 3: armed + target
    assert ei.value.replica == 1
    plan.on_dispatch(replica=1)              # spent: at most once
    assert len(plan.fired) == 1


def test_fault_plan_replica_wedge_sleeps():
    plan = FaultPlan([FaultSpec("replica_wedge", replica=0, wedge_s=0.02)])
    t0 = time.monotonic()
    plan.on_dispatch(replica=0)
    assert time.monotonic() - t0 >= 0.02
    assert plan.fired


def test_replica_health_circuit_breaker_threshold():
    h = ReplicaHealth(2, fail_threshold=2)
    assert h.live() == [0, 1] and h.state(0) == ReplicaHealth.LIVE
    assert not h.record_failure(0)           # 1 of 2: still live
    assert h.live() == [0, 1]
    assert h.record_failure(0)               # 2 of 2: trips
    assert h.state(0) == ReplicaHealth.DEAD
    assert h.live() == [1] and h.dead() == [0]
    assert not h.record_failure(0)           # already dead: no-op
    # success resets the consecutive count of a live replica
    h2 = ReplicaHealth(1, fail_threshold=2)
    h2.record_failure(0)
    h2.record_success(0)
    assert not h2.record_failure(0)          # streak restarted


def test_replica_health_half_open_probe_cycle():
    h = ReplicaHealth(2, fail_threshold=1)
    assert h.mark_dead(1)                    # unconditional kill
    assert not h.mark_dead(1)                # idempotent
    h.mark_resynced(1)
    assert h.state(1) == ReplicaHealth.HALF_OPEN
    assert h.half_open() == [1]
    assert h.live() == [0]                   # half-open is NOT routable-live
    h.record_success(1)                      # probe succeeded
    assert h.state(1) == ReplicaHealth.LIVE
    # a failed probe drops straight back to dead regardless of threshold
    h.mark_dead(1)
    h.mark_resynced(1)
    assert h.record_failure(1)
    assert h.state(1) == ReplicaHealth.DEAD


def test_replica_health_validates():
    with pytest.raises(ValueError):
        ReplicaHealth(0)
    with pytest.raises(ValueError):
        ReplicaHealth(2, fail_threshold=0)
    h = ReplicaHealth(2)
    h.mark_resynced(0)                       # live: no-op, not half-open
    assert h.state(0) == ReplicaHealth.LIVE
