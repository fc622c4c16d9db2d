"""Fleet router: prefix-aware dispatch over N serve-engine replicas.

Differential conformance in the style of tests/conformance.py: every
registered traffic trace replays through a 1-replica fleet and an
N-replica fleet, and the fleet must be observationally identical -
bit-identical per-request greedy outputs (replicas share jitted steps,
so the comparison is exact, with the teacher-forced near-tie fallback),
per-replica page conservation after every tick and after the drain
(replay_fleet), and work-clock comparability (equal generated tokens on
every trace; byte-equal work totals on traces where no prefix cache or
preemption can legitimately shift executed work between topologies).

Plus the router's own policy surface: affinity routing follows cached
prefixes (via the side-effect-free peek), round-robin ignores them,
spill-to-next-best under the per-replica admission cap, deterministic
tie-breaking (bit-reproducible replays), and the fleet telemetry view
(summed registries, dispatch/spill/affinity counters, merged Perfetto
trace with one track group per replica).
"""
import json

import jax
import pytest

from conformance import TRACES, make_scfg
from repro.configs import get_smoke_config
from repro.configs.base import ServeConfig
from repro.models import build_model
from repro.serve import FleetConfig, FleetRouter, ServeEngine
from traffic import (TrafficItem, assert_greedy_equivalent, mixed_prompts,
                     replay, replay_fleet, shared_prefix_prompts)


@pytest.fixture(scope="module")
def model_f32():
    # float32 keeps greedy argmax ties out of the parity comparisons
    cfg = get_smoke_config("granite-3-2b").replace(dtype="float32")
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _fleet(model, params, n, scfg, **fcfg_kw):
    return FleetRouter(model, params, scfg,
                       FleetConfig(n_replicas=n, **fcfg_kw))


def _affinity_scfg(**over):
    base = dict(max_batch=4, max_seq=512, page_size=16, prefill_chunk=32,
                tick_token_budget=64, max_new_tokens=8, paged=True,
                prefix_cache=True)
    base.update(over)
    return ServeConfig(**base)


def _replay_fleet(model, params, trace, n, **fcfg_kw):
    scfg = make_scfg(trace, False, max_new_tokens=12)
    router = _fleet(model, params, n, scfg, **fcfg_kw)
    out, _ = replay_fleet(router, trace.build(model.cfg.vocab_size),
                          check=True)
    return out, router


# ===========================================================================
# differential conformance: 1-replica fleet vs N-replica fleet
# ===========================================================================

@pytest.mark.parametrize("trace", sorted(TRACES))
def test_fleet_differential_1_vs_2_replicas(trace, model_f32):
    """The tentpole guarantee: the same trace through a 1-replica and a
    2-replica fleet yields bit-identical per-request greedy outputs (the
    replicas run the very same compiled steps), equal generated-token
    totals, and - on traces where neither prefix-cache interleaving nor
    preemption can shift executed work between topologies - byte-equal
    summed work clocks.  Page conservation on every replica is checked
    per tick and after the drain inside replay_fleet."""
    m, params = model_f32
    spec = TRACES[trace]
    out1, r1 = _replay_fleet(m, params, spec, 1)
    out2, r2 = _replay_fleet(m, params, spec, 2)
    assert out1.keys() == out2.keys()
    if out1 != out2:
        # only genuine fp argmax near-ties may differ; anything else
        # (corrupted KV, lost chunk, wrong routing bookkeeping) fails
        assert_greedy_equivalent(m, params, list(r2.requests.values()),
                                 out1)
    s1, s2 = r1.fleet_stats(), r2.fleet_stats()
    assert s1["requests"] == s2["requests"] == len(out1)
    assert s1["gen_tokens"] == s2["gen_tokens"]
    deterministic_work = not spec.scfg_kw.get("prefix_cache") \
        and not spec.scfg_kw.get("preemption")
    if deterministic_work:
        assert s1["work_tokens"] == s2["work_tokens"], \
            (s1["work_tokens"], s2["work_tokens"])
    # every request landed somewhere, and dispatch accounting closed
    assert sum(s2["dispatch"]) == len(out2)
    r1.check_invariants()
    r2.check_invariants()


def test_one_replica_fleet_matches_bare_engine(model_f32):
    """A 1-replica fleet is the engine: the router layer must add zero
    behavior - same outputs, same work clock, same prefill totals."""
    m, params = model_f32
    spec = TRACES["mixed"]
    scfg = make_scfg(spec, False, max_new_tokens=12)
    eng = ServeEngine(m, params, scfg)
    out_e, _ = replay(eng, spec.build(m.cfg.vocab_size), check=True)
    out_f, router = _replay_fleet(m, params, spec, 1)
    # engine uids and fleet uids are both monotone from 1 in submit order
    assert out_e == out_f
    se, sf = eng.stats(), router.fleet_stats()
    for k in ("work_tokens", "gen_tokens", "prefill_tokens", "requests"):
        assert se[k] == sf[k], (k, se[k], sf[k])


# ===========================================================================
# routing policy: affinity, round-robin, spill, determinism
# ===========================================================================

def test_affinity_routes_followers_to_the_warm_replica(model_f32):
    """After one request warms a replica's prefix tree, every follower
    sharing that prefix must land on the SAME replica (cache-hit-weighted
    score beats the load imbalance it creates), and actually hit: the
    home replica's prefix counters record the reuse, the router's
    affinity counters record the decisions."""
    m, params = model_f32
    prompts = shared_prefix_prompts(m.cfg.vocab_size, 128, (16, 24, 32))
    router = _fleet(m, params, 2, _affinity_scfg())
    warm_uid = router.submit(prompts[0])
    router.run_until_done()
    home = router.placement[warm_uid]
    follower_uids = [router.submit(p) for p in prompts[1:]]
    router.run_until_done()
    assert all(router.placement[u] == home for u in follower_uids), \
        "a follower was routed off its cached prefix"
    st = router.fleet_stats()
    # each follower shares exactly 128 tokens = 8 whole pages with the
    # warm prompt, and the peek-based accounting saw it at dispatch
    assert st["affinity_hits"] == len(follower_uids)
    assert st["affinity_hit_tokens"] == 128 * len(follower_uids)
    assert router.engines[home].prefix_hit_tokens >= 128 * len(follower_uids)
    cold = router.engines[1 - home]
    assert cold.prefix_hit_tokens == 0
    # the control: the same warm-then-followers trace round-robin routes
    # followers off the cached prefix, so it recomputes prefill that
    # affinity saves - with the same greedy outputs
    rr = _fleet(m, params, 2, _affinity_scfg(), policy="round_robin")
    rr.submit(prompts[0])
    rr.run_until_done()
    for p in prompts[1:]:
        rr.submit(p)
    rr.run_until_done()
    assert rr.outputs() == router.outputs()

    def prefill(fleet):
        return sum(e.prefill_tokens for e in fleet.engines)

    assert prefill(router) < prefill(rr)


def test_round_robin_ignores_the_cache(model_f32):
    """The control policy: round-robin alternates replicas regardless of
    where prefixes live - the bench's baseline for 'affinity actually
    buys something'."""
    m, params = model_f32
    prompts = mixed_prompts(m.cfg.vocab_size, lens=(8, 8, 8, 8))
    router = _fleet(m, params, 2, _affinity_scfg(), policy="round_robin")
    uids = [router.submit(p) for p in prompts]
    assert [router.placement[u] for u in uids] == [0, 1, 0, 1]
    router.run_until_done()
    assert router.dispatch_counts() == [2, 2]


def test_spill_to_next_best_under_admission_cap(model_f32):
    """Per-replica admission backpressure: with spill_queue_depth=1, a
    second follower bound for the warm (best-scoring) replica spills to
    the next-best one instead of queueing behind the first - counted in
    fleet_spills_total - and when EVERY replica is at the cap the best
    one still absorbs the request (the cap sheds imbalance, it never
    rejects work)."""
    m, params = model_f32
    prompts = shared_prefix_prompts(m.cfg.vocab_size, 128, (16, 24, 32))
    router = _fleet(m, params, 2, _affinity_scfg(), spill_queue_depth=1)
    warm_uid = router.submit(prompts[0])
    router.run_until_done()
    home = router.placement[warm_uid]
    u1 = router.submit(prompts[1])      # home queue: 0 -> placed home
    u2 = router.submit(prompts[2])      # home at cap -> spills
    assert router.placement[u1] == home
    assert router.placement[u2] == 1 - home
    assert router.metrics.get("fleet_spills_total").value == 1
    # both replicas now at the cap: the best-scoring one absorbs anyway
    u3 = router.submit(prompts[1][:32])
    assert router.placement[u3] == home
    router.run_until_done()
    router.check_invariants()


def test_dispatch_is_deterministic_across_replays(model_f32):
    """Bit-reproducible replays: two routers fed the identical timed
    trace make identical placements (ties break to the lowest replica
    index; every score input is deterministic host state) and produce
    identical outputs."""
    m, params = model_f32
    spec = TRACES["wave"]

    def run():
        scfg = make_scfg(spec, False, max_new_tokens=8)
        router = _fleet(m, params, 3, scfg)
        out, _ = replay_fleet(router, spec.build(m.cfg.vocab_size),
                              check=False)
        return out, dict(router.placement), router.dispatch_counts()

    out_a, place_a, counts_a = run()
    out_b, place_b, counts_b = run()
    assert place_a == place_b
    assert counts_a == counts_b
    assert out_a == out_b


# ===========================================================================
# fleet telemetry: summed registries, merged Perfetto trace
# ===========================================================================

def test_fleet_snapshot_sums_replica_registries(model_f32):
    """fleet_snapshot() is the fleet registry view: router metrics, every
    replica's full snapshot, and a summed section whose counters equal
    the per-replica totals (the fleet_stats aggregates agree with it)."""
    m, params = model_f32
    spec = TRACES["mixed"]
    out, router = _replay_fleet(m, params, spec, 2)
    snap = router.fleet_snapshot()
    assert set(snap) == {"router", "replicas", "sum"}
    assert len(snap["replicas"]) == 2
    gen_per_replica = sum(e.gen_tokens for e in router.engines)
    assert snap["sum"]["serve_gen_tokens_total"] == gen_per_replica
    assert router.fleet_stats()["gen_tokens"] == gen_per_replica
    assert snap["router"]["fleet_requests_total"]["value"] == len(out)
    assert snap["router"]["fleet_replicas"]["value"] == 2
    # labeled dispatch counters survive the summing path per label
    dispatch = snap["router"]["fleet_dispatch_total"]["value"]
    assert sum(dispatch.values()) == len(out)


def test_merged_perfetto_trace_one_track_group_per_replica(model_f32,
                                                          tmp_path):
    """export_trace merges every replica's Chrome trace into one file:
    pids offset per replica, process names `replicaN:engine` /
    `replicaN:requests`, real (non-metadata) events present for every
    replica, written on the deterministic work clock."""
    m, params = model_f32
    prompts = mixed_prompts(m.cfg.vocab_size, lens=(16, 24, 16, 24))
    router = _fleet(m, params, 2, _affinity_scfg(telemetry=True))
    for p in prompts:
        router.submit(p)
    router.run_until_done()
    path = tmp_path / "fleet_trace.json"
    trace = router.export_trace(str(path), clock="work")
    on_disk = json.loads(path.read_text())
    assert on_disk == trace
    assert trace["otherData"]["n_replicas"] == 2
    names = {ev["args"]["name"] for ev in trace["traceEvents"]
             if ev.get("ph") == "M" and ev.get("name") == "process_name"}
    assert names == {"replica0:engine", "replica0:requests",
                     "replica1:engine", "replica1:requests"}
    real_pids = {ev["pid"] for ev in trace["traceEvents"]
                 if ev.get("ph") != "M"}
    # engine tick spans exist for both replicas (pids 0 and 2)
    assert {0, 2} <= real_pids
    assert real_pids <= {0, 1, 2, 3}


def test_engine_load_stats_is_cheap_and_registry_backed(model_f32):
    """The router's per-submit load probe: correct occupancy arithmetic,
    zero device->host syncs, and the work-token total published to the
    `serve_outstanding_work_tokens` gauge."""
    m, params = model_f32
    eng = ServeEngine(m, params, _affinity_scfg())
    syncs0 = eng.host_syncs
    ls = eng.load_stats()
    assert ls == {"queue_depth": 0, "inflight": 0, "free_slots": 4,
                  "outstanding_work_tokens": 0,
                  "free_pages": ls["free_pages"], "evictable_pages": 0}
    eng.submit([1, 2, 3, 4], max_new_tokens=6)
    ls = eng.load_stats()
    assert ls["queue_depth"] == 1
    assert ls["outstanding_work_tokens"] == 4 + 6
    assert eng.tm.registry.get("serve_outstanding_work_tokens").value \
        == 10
    assert eng.host_syncs == syncs0, "load_stats touched the device"


def test_fleet_config_validation():
    with pytest.raises(ValueError, match="n_replicas"):
        FleetConfig(n_replicas=0).validate()
    with pytest.raises(ValueError, match="policy"):
        FleetConfig(policy="sticky-random").validate()
    with pytest.raises(ValueError, match="spill_queue_depth"):
        FleetConfig(spill_queue_depth=-1).validate()
    with pytest.raises(ValueError, match="weights"):
        FleetConfig(load_weight=-0.5).validate()
    with pytest.raises(ValueError, match="weights"):
        FleetConfig(slo_weight=-0.1).validate()
    with pytest.raises(ValueError, match="watchdog_ticks"):
        FleetConfig(watchdog_ticks=-1).validate()


# ===========================================================================
# replica lifecycle: drain under load, SLO shedding, exhaust reporting
# ===========================================================================

def test_drain_under_load_empties_and_matches_no_drain_run(model_f32):
    """The drain contract under real load: a replica holding BOTH
    prefilling and decoding requests is drained mid-flight; it takes no
    new work, finishes what it holds in place, every non-cached page
    returns to its pool, and the fleet's outputs are bit-identical to a
    run that never drained."""
    m, params = model_f32
    from repro.serve import ReplicaState
    spec = TRACES["mixed"]
    scfg = make_scfg(spec, False, max_new_tokens=12)
    items = spec.build(m.cfg.vocab_size)

    base_router = _fleet(m, params, 2, scfg)
    base_out, _ = replay_fleet(base_router, [TrafficItem(0, it.prompt)
                                             for it in items], check=True)

    router = _fleet(m, params, 2, scfg)
    for it in items:
        router.submit(it.prompt)
    # a couple of ticks in, replica 0 holds a mix of prefilling (long
    # prompts chunk across ticks) and decoding (short prompts) requests
    for _ in range(2):
        router.tick()
    from repro.serve.scheduler import RequestState
    eng0 = router.engines[0]
    live = [s for s in eng0.slots if s is not None]
    assert live, "trace never put in-flight work on replica 0"
    states = {r.state for r in live} | {r.state for r in eng0.queue}
    assert states & {RequestState.PREFILLING, RequestState.DECODING}, states
    router.drain(0)
    assert router.states[0] is ReplicaState.DRAINING
    pre_dispatch = router.dispatch_counts()
    # more traffic while draining: ALL of it must land on replica 1
    extra = [router.submit(list(range(50 + i, 90 + i))) for i in range(3)]
    assert all(router.placement[f] == 1 for f in extra)
    assert router.dispatch_counts()[0] == pre_dispatch[0]
    router.run_until_done()
    router.check_invariants()
    # the drained replica emptied in place and its pages came home
    assert not eng0.queue and all(s is None for s in eng0.slots)
    cached = eng0.prefix.cached_pages if eng0.prefix is not None else 0
    assert eng0.allocator.used_pages == cached
    # and the drain changed no tokens on the original trace
    got = {f: o for f, o in router.outputs().items() if f in base_out}
    if got != base_out:
        reqs = [router.requests[f] for f in base_out]
        assert_greedy_equivalent(m, params, reqs, base_out)


def test_slo_weight_sheds_load_off_a_slow_replica(model_f32):
    """The SLO dispatch term: a replica whose delivered work-clock p95
    TTFT is large loses otherwise-tied dispatches once slo_weight > 0 -
    and keeps winning ties (lowest index) when slo_weight stays 0."""
    from repro.serve.scheduler import Request

    def seed_slow_history(router, ridx, ttft):
        # fabricate a finished request whose first token cost `ttft`
        # work tokens - the shape _observed_ttft() reads
        r = Request(uid=900, prompt=[1, 2], max_new_tokens=1)
        r.w_submit = 0
        r.token_work = [ttft]
        r.done = True
        router.engines[ridx].sched.finished.append(r)

    m, params = model_f32
    scfg = _affinity_scfg(prefix_cache=False)
    prompt = list(range(1, 40))

    router = _fleet(m, params, 2, scfg)            # slo_weight=0 control
    seed_slow_history(router, 0, 500)
    uid = router.submit(prompt)
    assert router.placement[uid] == 0, "tie must break to lowest index"

    router = _fleet(m, params, 2, scfg, slo_weight=1.0)
    seed_slow_history(router, 0, 500)
    uid = router.submit(prompt)
    assert router.placement[uid] == 1, \
        "slo_weight must shed load off the slow replica"
    # symmetric histories tie again: back to the index tie-break
    router = _fleet(m, params, 2, scfg, slo_weight=1.0)
    seed_slow_history(router, 0, 500)
    seed_slow_history(router, 1, 500)
    uid = router.submit(prompt)
    assert router.placement[uid] == 0


def test_run_until_done_exhaust_reports_statuses(model_f32):
    """on_exhaust="return" must tell the caller WHAT state every request
    is in - per-status counts and the still-running fleet uids - not
    just that ticks ran out."""
    m, params = model_f32
    scfg = make_scfg(TRACES["mixed"], False, max_new_tokens=24)
    router = _fleet(m, params, 2, scfg)
    uids = [router.submit(list(range(1, 120))) for _ in range(3)]
    with pytest.warns(UserWarning) as rec:
        router.run_until_done(max_ticks=1, on_exhaust="return")
    msg = str(rec[0].message)
    assert "statuses" in msg and "still running fleet uids" in msg
    for uid in uids:
        assert router.statuses()[uid] != "done"
    with pytest.raises(RuntimeError):
        router.run_until_done(max_ticks=1)


def test_every_router_metric_is_documented(model_f32):
    """Doc-coverage for the ROUTER registry: docs/routing.md must name
    every metric the router registers (same contract the engine registry
    has with docs/observability.md)."""
    from pathlib import Path
    m, params = model_f32
    router = _fleet(m, params, 2, _affinity_scfg())
    text = (Path(__file__).resolve().parents[1]
            / "docs" / "routing.md").read_text()
    missing = [n for n in router.metrics.names() if f"`{n}`" not in text]
    assert not missing, \
        f"router metrics missing from docs/routing.md: {missing}"
