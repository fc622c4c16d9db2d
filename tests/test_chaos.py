"""Chaos suite: deterministic fault injection against the fleet router.

Every scenario drives a fleet through a registered traffic trace while a
seeded FaultPlan kills, drains, wedges, or page-starves replicas at
fixed tick indices, and asserts the full invariant sweep every tick
(survivor page conservation, dispatch/redispatch ledger, work-clock
monotonicity, no duplicated terminals) plus the chaos conformance
contract: every request that finishes DONE produces output identical to
a fault-free run of the same trace - replica death is invisible in the
tokens, visible only in telemetry and latency.
"""
import jax
import numpy as np
import pytest

from chaos import (Fault, FaultPlan, assert_chaos_conformance,
                   random_fault_plan, replay_fleet_chaos)
from conformance import TRACES, make_scfg
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import FleetConfig, FleetRouter, ReplicaState
from traffic import TrafficItem, replay_fleet


@pytest.fixture(scope="module")
def model_f32():
    # float32 keeps greedy argmax ties out of the conformance comparisons
    cfg = get_smoke_config("granite-3-2b").replace(dtype="float32")
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _fleet(model, params, scfg, n, **fcfg_kw):
    return FleetRouter(model, params, scfg,
                       FleetConfig(n_replicas=n, **fcfg_kw))


def _baseline(model, params, spec, n=2, **scfg_kw):
    """Fault-free reference run: same trace, same fleet size."""
    scfg = make_scfg(spec, False, max_new_tokens=12, **scfg_kw)
    router = _fleet(model, params, scfg, n)
    out, _ = replay_fleet(router, spec.build(model.cfg.vocab_size),
                          check=True)
    return out, scfg


# ===========================================================================
# the tentpole: kill one replica mid-trace, every registered trace
# ===========================================================================

@pytest.mark.parametrize("trace", sorted(TRACES))
def test_kill_replica_mid_trace_is_invisible_in_outputs(trace, model_f32):
    """Replica death mid-flight must not change a single token: queued
    and in-flight requests redispatch to the survivor through the resume
    path (prompt + generated-so-far re-prefilled through the chunk path)
    and every request completes with output identical to the fault-free
    run.  Invariants sweep every tick inside replay_fleet_chaos."""
    m, params = model_f32
    spec = TRACES[trace]
    base, scfg = _baseline(m, params, spec)
    router = _fleet(m, params, scfg, 2)
    plan = FaultPlan([Fault(2, "kill", 1)])
    out, done = replay_fleet_chaos(router, spec.build(m.cfg.vocab_size),
                                   plan)
    # nothing lost, nothing timed out: every request completed DONE
    assert set(router.statuses().values()) == {"done"}
    done_uids = assert_chaos_conformance(m, params, router, done, base)
    assert done_uids == base.keys()
    s = router.fleet_stats()
    assert s["failures"] == 1
    assert s["replica_states"] == ["healthy", "dead"]


def _tick_to_empty(router, before_tick=lambda tick: None):
    """Tick the fleet until it is idle, calling before_tick(t) ahead of
    tick t; returns {fleet uid: the fleet tick that brought its first
    token} - the one clock that spans a redispatch."""
    first, tick = {}, 0
    while not router.idle:
        before_tick(tick)
        router.tick()
        tick += 1
        for fuid, r in router.requests.items():
            if r.out_tokens:
                first.setdefault(fuid, tick)
    return first


def test_kill_redispatches_queued_and_in_flight(model_f32):
    """fail() moves EVERYTHING the dead replica owed: requests still
    queued and requests mid-prefill/mid-decode - each keeps its fleet
    uid, lands on a survivor, and carries its redispatch count.  The
    recovery's latency cost is bounded: the p95 first-token latency in
    fleet ticks at most triples against the fault-free run."""
    m, params = model_f32
    spec = TRACES["mixed"]
    scfg = make_scfg(spec, False, max_new_tokens=12)
    prompts = [it.prompt for it in spec.build(m.cfg.vocab_size)]
    fault_free = _fleet(m, params, scfg, 2)
    router = _fleet(m, params, scfg, 2)
    for p in prompts:
        fault_free.submit(p)
        router.submit(p)

    def kill_after_two_ticks(tick):
        if tick != 2:
            return
        victims = sorted(f for f, r in router.placement.items()
                         if r == 1 and not router.requests[f].done)
        assert victims, "trace never placed work on replica 1"
        moved = router.fail(1)
        assert moved == victims
        for fuid in moved:
            assert router.placement[fuid] == 0
            assert router.requests[fuid].n_redispatches == 1
        # idempotent: a second fail of the corpse is a no-op
        assert router.fail(1) == []

    ttft_base = _tick_to_empty(fault_free)
    ttft = _tick_to_empty(router, kill_after_two_ticks)
    assert router.fleet_stats()["failures"] == 1
    assert set(router.statuses().values()) == {"done"}
    router.check_invariants()
    assert ttft.keys() == ttft_base.keys()
    p95_base = max(float(np.percentile(list(ttft_base.values()), 95)), 1.0)
    assert float(np.percentile(list(ttft.values()), 95)) <= 3.0 * p95_base


def test_no_healthy_replica_raises(model_f32):
    """Dispatch with every replica dead/draining must fail loudly, not
    hang or place work on a corpse."""
    m, params = model_f32
    scfg = make_scfg(TRACES["mixed"], False, max_new_tokens=4)
    router = _fleet(m, params, scfg, 2)
    router.fail(0)
    router.drain(1)
    with pytest.raises(RuntimeError, match="no healthy replica"):
        router.submit([1, 2, 3])
    # and a dead replica cannot drain or rejoin
    with pytest.raises(ValueError):
        router.drain(0)
    with pytest.raises(ValueError):
        router.undrain(0)


# ===========================================================================
# drain lifecycle
# ===========================================================================

def test_drain_to_empty_then_undrain_conformance(model_f32):
    """A drain mid-trace stops new dispatch to the replica, lets it
    empty in place, and changes no output; once empty the drain duration
    lands in the histogram and the replica stays parked DRAINING until
    undrain returns it to rotation."""
    m, params = model_f32
    spec = TRACES["mixed"]
    base, scfg = _baseline(m, params, spec)
    router = _fleet(m, params, scfg, 2)
    plan = FaultPlan([Fault(1, "drain", 0)])
    out, done = replay_fleet_chaos(router, spec.build(m.cfg.vocab_size),
                                   plan)
    assert set(router.statuses().values()) == {"done"}
    assert_chaos_conformance(m, params, router, done, base)
    s = router.fleet_stats()
    assert s["drains"] == 1
    assert s["replica_states"] == ["draining", "healthy"]
    hist = router.metrics.get("fleet_drain_duration_ticks")
    assert hist.count == 1
    router.undrain(0)
    assert router.states[0] is ReplicaState.HEALTHY
    # back in rotation: the undrained replica can take new work
    uid = router.submit([7, 8, 9, 10])
    router.run_until_done()
    assert router.statuses()[uid] == "done"


# ===========================================================================
# watchdog: stuck tick -> declared dead -> redispatch
# ===========================================================================

def test_stuck_tick_trips_watchdog_and_recovers(model_f32):
    """A replica that holds work but stops making progress (tick stubbed
    to a no-op, work clock frozen) is declared DEAD after watchdog_ticks
    stale fleet ticks; its requests redispatch and the trace completes
    with fault-free outputs."""
    m, params = model_f32
    spec = TRACES["mixed"]
    base, scfg = _baseline(m, params, spec)
    router = _fleet(m, params, scfg, 2, watchdog_ticks=3)
    plan = FaultPlan([Fault(2, "stuck", 1)])
    out, done = replay_fleet_chaos(router, spec.build(m.cfg.vocab_size),
                                   plan)
    assert set(router.statuses().values()) == {"done"}
    assert_chaos_conformance(m, params, router, done, base)
    assert int(router.metrics.get("fleet_watchdog_trips_total").value) == 1
    assert router.states[1] is ReplicaState.DEAD
    assert router.fleet_stats()["redispatches"] >= 1


def test_watchdog_ignores_idle_replicas(model_f32):
    """An EMPTY replica with a frozen work clock is idle, not wedged -
    the watchdog must never kill it."""
    m, params = model_f32
    scfg = make_scfg(TRACES["mixed"], False, max_new_tokens=4)
    router = _fleet(m, params, scfg, 2, watchdog_ticks=2)
    router.submit([1, 2, 3, 4])           # lands on one replica only
    router.run_until_done()
    for _ in range(6):                    # idle ticks, clocks frozen
        router.tick()
    assert router.states == [ReplicaState.HEALTHY, ReplicaState.HEALTHY]
    assert int(router.metrics.get("fleet_watchdog_trips_total").value) == 0


# ===========================================================================
# page-pool exhaustion (sanctioned quarantine)
# ===========================================================================

def test_pool_squeeze_under_preemption_conformance(model_f32):
    """Quarantining free pages mid-trace (deterministic pool exhaustion)
    squeezes the replica exactly like a smaller pool: preemption absorbs
    the pressure, allocator invariants hold THROUGH the squeeze (the
    conservation sum counts quarantined pages), and outputs match the
    fault-free run after the restore."""
    m, params = model_f32
    spec = TRACES["mixed"]
    base, scfg = _baseline(m, params, spec, preemption=True,
                           prefix_cache=True)
    router = _fleet(m, params, scfg, 2)
    plan = FaultPlan([Fault(2, "pool_squeeze", 0, pages=10),
                      Fault(8, "pool_restore", 0)])
    out, done = replay_fleet_chaos(router, spec.build(m.cfg.vocab_size),
                                   plan)
    assert set(router.statuses().values()) == {"done"}
    assert_chaos_conformance(m, params, router, done, base)
    assert router.engines[0].allocator.quarantined_pages == 0


# ===========================================================================
# deadlines and retry budgets under chaos
# ===========================================================================

def test_deadline_expiry_is_terminal_not_a_hang(model_f32):
    """A request whose work-clock deadline lands mid-prefill finishes
    TIMEOUT - pages freed, terminal status surfaced - while unrelated
    traffic completes untouched."""
    m, params = model_f32
    scfg = make_scfg(TRACES["mixed"], False, max_new_tokens=12)
    items = [TrafficItem(0, list(range(1, 129)), deadline=140),
             TrafficItem(0, list(range(200, 232)))]
    router = _fleet(m, params, scfg, 1)
    out, done = replay_fleet_chaos(router, items, FaultPlan([]))
    assert router.statuses() == {1: "timeout", 2: "done"}
    timed_out = router.requests[1]
    assert timed_out.finish_reason == "timeout"
    assert router.fleet_stats()["timeouts"] == 1


def test_retry_budget_exhaustion_goes_failed(model_f32):
    """max_retries=0 requests on a killed replica go terminal FAILED
    (no redispatch), surface in statuses()/outputs() and the finished
    stream, and the retries-exhausted counter accounts for each."""
    m, params = model_f32
    scfg = make_scfg(TRACES["mixed"], False, max_new_tokens=8)
    items = [TrafficItem(0, list(range(1 + i, 33 + i)), max_retries=0)
             for i in range(3)]
    router = _fleet(m, params, scfg, 2)
    plan = FaultPlan([Fault(1, "kill", 0)])
    out, done = replay_fleet_chaos(router, items, plan)
    statuses = router.statuses()
    failed = {f for f, s in statuses.items() if s == "failed"}
    assert failed, "the kill never caught a max_retries=0 request"
    assert set(statuses.values()) <= {"done", "failed"}
    assert int(router.metrics.get(
        "fleet_retries_exhausted_total").value) == len(failed)
    # FAILED requests still appear exactly once in the finished stream
    assert sorted(r.fleet_uid for r in done) == sorted(statuses)
    for f in failed:
        assert router.requests[f].finish_reason == "failed"


def test_retry_budget_allows_n_redispatches(model_f32):
    """max_retries=1 survives one kill (redispatch) and dies on the
    second: the budget counts moves, not submissions."""
    m, params = model_f32
    scfg = make_scfg(TRACES["mixed"], False, max_new_tokens=64)
    router = _fleet(m, params, scfg, 3)
    uid = router.submit(list(range(1, 200)), max_retries=1)
    router.tick()
    router.fail(router.placement[uid])
    assert router.requests[uid].n_redispatches == 1
    assert router.statuses()[uid] != "failed"
    router.tick()
    router.fail(router.placement[uid])
    assert router.statuses()[uid] == "failed"


# ===========================================================================
# seeded random chaos soak
# ===========================================================================

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_chaos_soak(seed, model_f32):
    """Seeded random FaultPlans (kills, drains, pool squeezes - always
    leaving a healthy survivor) over a registered trace: the fleet must
    drain with every request terminal, invariants green every tick, and
    every DONE output identical to the fault-free run."""
    m, params = model_f32
    spec = TRACES["mixed"]
    base, scfg = _baseline(m, params, spec, n=3)
    plan = random_fault_plan(seed, n_replicas=3, max_tick=10)
    router = _fleet(m, params, scfg, 3, watchdog_ticks=4)
    out, done = replay_fleet_chaos(router, spec.build(m.cfg.vocab_size),
                                   plan)
    assert_chaos_conformance(m, params, router, done, base)
    # same seed -> same plan: the soak is replayable, not flaky
    again = random_fault_plan(seed, n_replicas=3, max_tick=10)
    assert again.faults == plan.faults


# ===========================================================================
# tensor-parallel replicas under chaos (docs/tensor_parallel.md)
# ===========================================================================

@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
def test_kill_tp_replica_mid_trace_is_invisible_in_outputs(model_f32):
    """The TP chaos contract: killing one head-sharded (tp_degree=2)
    replica mid-trace leaves the survivors' outputs bit-identical to a
    fault-free run of the same TP fleet - redispatch, resume, and the
    sharded kernels compose.  The per-shard byte accounting sweeps every
    tick (engine check_invariants inside replay_fleet_chaos) and must
    still hold on the survivor after the drain."""
    from conformance import assert_tp_shard_accounting

    m, params = model_f32
    spec = TRACES["mixed"]
    base, scfg = _baseline(m, params, spec, tp_degree=2)
    router = _fleet(m, params, scfg, 2)
    plan = FaultPlan([Fault(2, "kill", 1)])
    out, done = replay_fleet_chaos(router, spec.build(m.cfg.vocab_size),
                                   plan)
    assert set(router.statuses().values()) == {"done"}
    done_uids = assert_chaos_conformance(m, params, router, done, base)
    assert done_uids == base.keys()
    survivor = router.engines[0]
    assert survivor.tp_stats()["tp_degree"] == 2
    assert_tp_shard_accounting(survivor)
    s = router.fleet_stats()
    assert s["failures"] == 1
    assert s["replica_states"] == ["healthy", "dead"]
