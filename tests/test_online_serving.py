"""Online serving layer: windowed scheduling under a rolling budget, circuit
breaking + rescheduling, response caching, duplicate coalescing, replica
failover, capacity caps, and real-time pacing."""
import itertools
import threading
import time

import numpy as np
import pytest

from repro.core.problem import group_into_batches
from repro.core.scheduler import greedy_schedule, greedy_schedule_window, restrict_space
from repro.data.simulator import BatchResult
from repro.serving.fault import BreakerPolicy, CircuitState, FlakyMember, ReplicaPolicy
from repro.serving.online import (
    FakeClock,
    OnlineConfig,
    OnlineRobatchServer,
    arrival_stream,
    poisson_arrivals,
)
from repro.serving.pool import ReplicaSet, replicate_simulated


def _rate(rb, test_idx, qps, budget_x=3.0):
    base = float(rb.cost_model.state_cost(0, rb.calibrations[0].b_effect,
                                          test_idx).mean())
    return qps * base * budget_x


def _server(rb, pool, wl, *, qps=40.0, budget_x=3.0, window_s=0.25,
            threshold=1, recovery_s=1e9):
    test = wl.subset_indices("test")
    cfg = OnlineConfig(
        budget_per_s=_rate(rb, test, qps, budget_x), window_s=window_s,
        breaker=BreakerPolicy(failure_threshold=threshold,
                              recovery_time_s=recovery_s))
    return OnlineRobatchServer(rb, pool, wl, cfg)


# ---------------------------------------------------------------------------
# windowed scheduler
# ---------------------------------------------------------------------------

def test_windowed_scheduler_restricts_to_allowed_models(fitted_rb, agnews):
    test = agnews.subset_indices("test")[:32]
    space = fitted_rb.candidate_space(test)
    budget = float(space.cost[:, space.initial_state].sum()) * 4
    res = greedy_schedule_window(space, test, budget, allowed_models={1, 2})
    assert set(np.unique(res.assignment.model)) <= {1, 2}
    assert res.amortized_cost <= budget + 1e-9
    # the unrestricted schedule can only do at least as well
    full = greedy_schedule(space, test, budget)
    assert full.est_utility >= res.est_utility - 1e-9


def test_restrict_space_reanchors_when_anchor_model_trips(fitted_rb, agnews):
    test = agnews.subset_indices("test")[:16]
    space = fitted_rb.candidate_space(test)
    assert space.states[space.initial_state].model == 0
    sub = restrict_space(space, {1, 2})
    assert sub.states[sub.initial_state].model in {1, 2}
    # re-anchored initial state is the cheapest surviving column
    totals = sub.cost.sum(axis=0)
    assert np.argmin(totals) == sub.initial_state
    with pytest.raises(ValueError):
        restrict_space(space, set())


# ---------------------------------------------------------------------------
# rolling budget
# ---------------------------------------------------------------------------

def test_window_scheduling_respects_rolling_budget(fitted_rb, agnews, pool):
    srv = _server(fitted_rb, pool, agnews, qps=40.0, budget_x=2.0)
    rng = np.random.default_rng(0)
    arrivals = poisson_arrivals(rng, 40.0, 10.0, agnews.subset_indices("test"))
    stats = srv.run(arrivals)
    srv.close()
    assert stats.n_completed == stats.n_submitted
    # every round's committed (amortized) cost stayed within the bucket balance
    for w in stats.windows:
        if w.n_admitted:
            assert w.est_cost <= w.avail + 1e-9
    # realized total stays within the rolling allowance (small drift tolerance
    # for exact-vs-amortized partial batches)
    assert stats.total_cost <= stats.budget_allowance * 1.05 + 1e-9


def test_tight_budget_defers_instead_of_overspending(fitted_rb, agnews, pool):
    # a rate 10× lower must not spend more than its own allowance
    srv = _server(fitted_rb, pool, agnews, qps=40.0, budget_x=0.2)
    rng = np.random.default_rng(1)
    arrivals = poisson_arrivals(rng, 40.0, 10.0, agnews.subset_indices("test"))
    stats = srv.run(arrivals)
    srv.close()
    assert stats.total_cost <= stats.budget_allowance * 1.05 + 1e-9
    assert sum(w.n_deferred for w in stats.windows) > 0   # backpressure engaged


def test_zero_budget_sheds_all_queries(fitted_rb, agnews, pool):
    test = agnews.subset_indices("test")
    cfg = OnlineConfig(budget_per_s=0.0, window_s=0.25)
    srv = OnlineRobatchServer(fitted_rb, pool, agnews, cfg)
    stats = srv.run([(0.1 * i, int(q)) for i, q in enumerate(test[:8])])
    srv.close()
    assert stats.n_completed == stats.n_submitted
    assert stats.n_dropped == stats.n_submitted
    assert stats.total_cost == 0.0


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

def test_breaker_reschedules_to_surviving_models(fitted_rb, agnews, pool):
    flaky_k = 2
    pool_f = [FlakyMember(m, fail_from=0) if k == flaky_k else m
              for k, m in enumerate(pool)]
    srv = _server(fitted_rb, pool_f, agnews, qps=40.0, budget_x=4.0)
    rng = np.random.default_rng(2)
    arrivals = poisson_arrivals(rng, 40.0, 10.0, agnews.subset_indices("test"))
    stats = srv.run(arrivals)
    srv.close()
    assert srv.breakers[flaky_k].state == CircuitState.OPEN
    assert stats.n_reroutes > 0
    assert stats.n_completed == stats.n_submitted
    assert stats.n_dropped == 0                      # survivors absorbed everything
    served_on = {r.model for r in srv.completed if not r.dropped}
    assert flaky_k not in served_on


def test_anchor_model_outage_reanchors_and_survives(fitted_rb, agnews, pool):
    # model 0 anchors the upgrade chain; its outage exercises re-anchoring
    pool_f = [FlakyMember(pool[0], fail_from=2)] + list(pool[1:])
    srv = _server(fitted_rb, pool_f, agnews, qps=30.0, budget_x=6.0)
    rng = np.random.default_rng(3)
    arrivals = poisson_arrivals(rng, 30.0, 8.0, agnews.subset_indices("test"))
    stats = srv.run(arrivals)
    srv.close()
    assert srv.breakers[0].state == CircuitState.OPEN
    assert stats.n_completed == stats.n_submitted
    late = [r for r in srv.completed
            if not r.dropped and not r.cache_hit and r.n_reroutes > 0]
    assert late and all(r.model in {1, 2} for r in late)


def test_half_open_breaker_recovers_after_outage_ends(fitted_rb, agnews, pool):
    # outage spans calls [0, 3); the half-open probe after recovery_time
    # succeeds and the breaker closes, readmitting the model
    flaky_k = 0
    flaky = FlakyMember(pool[0], fail_from=0, fail_until=3)
    pool_f = [flaky] + list(pool[1:])
    srv = _server(fitted_rb, pool_f, agnews, qps=30.0, budget_x=4.0,
                  threshold=1, recovery_s=2.0)
    rng = np.random.default_rng(4)
    arrivals = poisson_arrivals(rng, 30.0, 12.0, agnews.subset_indices("test"))
    stats = srv.run(arrivals)
    srv.close()
    assert srv.breakers[flaky_k].state == CircuitState.CLOSED
    assert stats.n_completed == stats.n_submitted and stats.n_dropped == 0
    # the model serves real traffic again after recovery
    late = [r for r in srv.completed
            if r.model == flaky_k and not r.cache_hit and r.completed_at > 4.0]
    assert late


def test_half_open_probe_is_one_group_and_burns_no_reroute_budget(
        fitted_rb, agnews, pool):
    # permanently-down member with fast recovery probes: invocation count must
    # stay ~one per recovery period (no probe storms), and probe failures must
    # not drop queries through reroute exhaustion
    flaky = FlakyMember(pool[0], fail_from=0)        # never recovers
    pool_f = [flaky] + list(pool[1:])
    srv = _server(fitted_rb, pool_f, agnews, qps=30.0, budget_x=4.0,
                  threshold=1, recovery_s=1.0)
    rng = np.random.default_rng(5)
    arrivals = poisson_arrivals(rng, 30.0, 12.0, agnews.subset_indices("test"))
    stats = srv.run(arrivals)
    srv.close()
    assert stats.n_dropped == 0
    assert stats.n_completed == stats.n_submitted
    # 12s stream, 1s recovery: ≲ 1 initial failure + ~1 probe per period
    assert flaky.n_calls <= 16


# ---------------------------------------------------------------------------
# response cache + coalescing
# ---------------------------------------------------------------------------

def test_cache_hits_bill_zero_cost(fitted_rb, agnews, pool):
    srv = _server(fitted_rb, pool, agnews, qps=10.0, budget_x=5.0)
    q = int(agnews.subset_indices("test")[0])
    first = srv.submit(q, at=0.0)
    srv.step(1.0)
    assert first.completed_at is not None and not first.cache_hit
    spent_before = srv.bucket.total_spent
    assert spent_before > 0
    second = srv.submit(q, at=1.0)
    srv.step(2.0)
    srv.close()
    assert second.cache_hit and second.cost == 0.0
    assert second.utility == first.utility
    assert srv.bucket.total_spent == spent_before    # nothing new billed


def test_duplicates_coalesce_within_a_window(fitted_rb, agnews, pool):
    srv = _server(fitted_rb, pool, agnews, qps=10.0, budget_x=5.0)
    q = int(agnews.subset_indices("test")[1])
    r1, r2 = srv.submit(q, at=0.0), srv.submit(q, at=0.1)
    rep = srv.step(1.0)
    srv.close()
    assert rep.n_coalesced == 1 and rep.n_groups == 1
    assert r1.completed_at is not None and r2.completed_at is not None
    assert r1.utility == r2.utility
    assert r1.cost == r2.cost                        # same share of one bill


def test_poisson_arrivals_sorted_and_in_universe(agnews):
    rng = np.random.default_rng(0)
    test = agnews.subset_indices("test")
    arr = poisson_arrivals(rng, 25.0, 5.0, test, repeat_frac=0.5)
    ts = [t for t, _ in arr]
    assert ts == sorted(ts) and all(0 <= t < 5.0 for t in ts)
    assert all(int(q) in set(test.tolist()) for _, q in arr)


def test_arrival_generation_is_decoupled_from_run_length(agnews):
    # generation is pure in the rng: the bounded list is a prefix of the
    # unbounded stream, and the same seed replays the same stream
    test = agnews.subset_indices("test")
    bounded = poisson_arrivals(np.random.default_rng(9), 20.0, 4.0, test,
                               repeat_frac=0.3)
    unbounded = list(itertools.islice(
        arrival_stream(np.random.default_rng(9), 20.0, test, repeat_frac=0.3),
        len(bounded) + 10))
    assert bounded == unbounded[:len(bounded)]
    assert all(t >= 4.0 for t, _ in unbounded[len(bounded):len(bounded) + 1])
    again = poisson_arrivals(np.random.default_rng(9), 20.0, 4.0, test,
                             repeat_frac=0.3)
    assert bounded == again


# ---------------------------------------------------------------------------
# replica sets: least-loaded dispatch, failover, probe re-admission
# ---------------------------------------------------------------------------

class _FakeMember:
    """Pool member stub whose utilities identify which replica served."""

    def __init__(self, tag: float, block: threading.Event = None):
        self.name = "fake"
        self.c_in, self.c_out, self.context_len = 1.0, 2.0, 512
        self.tag = tag
        self.block = block
        self.n_calls = 0

    def invoke_batch(self, wl, batch_idx):
        self.n_calls += 1
        if self.block is not None:
            assert self.block.wait(timeout=10.0)
        return BatchResult(utilities=np.full(len(batch_idx), self.tag),
                           in_tokens=10, out_tokens=2, latency_s=0.01)


def test_replica_set_dispatches_to_least_loaded_replica():
    release = threading.Event()
    rs = ReplicaSet([_FakeMember(0.0, block=release), _FakeMember(1.0)],
                    name="m")
    first: dict = {}
    th = threading.Thread(
        target=lambda: first.setdefault("out", rs.invoke_batch(None, np.arange(2))))
    th.start()
    for _ in range(500):                      # replica 0 (index tie-break) busy
        if rs.loads() == [1, 0]:
            break
        time.sleep(0.005)
    assert rs.loads() == [1, 0]
    second = rs.invoke_batch(None, np.arange(2))   # least-loaded → replica 1
    assert float(second.utilities[0]) == 1.0
    release.set()
    th.join(timeout=10.0)
    assert float(first["out"].utilities[0]) == 0.0
    assert rs.loads() == [0, 0]


def test_replica_failure_retries_sibling_then_ejects():
    flaky = FlakyMember(_FakeMember(0.0), fail_from=0)   # replica 0 always dies
    rs = ReplicaSet([flaky, _FakeMember(1.0)], name="m")
    out = rs.invoke_batch(None, np.arange(3))            # retried on replica 1
    assert float(out.utilities[0]) == 1.0
    assert rs.tracker.replicas[0].n_failures == 1 and rs.tracker.healthy(0)
    out = rs.invoke_batch(None, np.arange(3))            # second strike ejects
    assert float(out.utilities[0]) == 1.0
    assert not rs.tracker.healthy(0) and rs.n_available() == 1
    n_flaky = flaky.n_calls
    rs.invoke_batch(None, np.arange(3))                  # ejected → not retried
    assert flaky.n_calls == n_flaky


def test_replica_set_raises_only_when_every_replica_fails():
    rs = ReplicaSet([FlakyMember(_FakeMember(0.0), fail_from=0),
                     FlakyMember(_FakeMember(1.0), fail_from=0)], name="m")
    with pytest.raises(RuntimeError, match="all 2 replicas"):
        rs.invoke_batch(None, np.arange(2))
    assert rs.tracker.replicas[0].n_failures == 1
    assert rs.tracker.replicas[1].n_failures == 1


def test_replica_probe_readmission_after_cooldown():
    now = [0.0]
    rs = ReplicaSet([FlakyMember(_FakeMember(0.0), fail_from=0, fail_until=1),
                     _FakeMember(1.0)],
                    name="m", policy=ReplicaPolicy(eject_after=1, cooldown_s=5.0),
                    clock=lambda: now[0])
    out = rs.invoke_batch(None, np.arange(2))     # replica 0 faults → ejected
    assert float(out.utilities[0]) == 1.0 and not rs.tracker.healthy(0)
    out = rs.invoke_batch(None, np.arange(2))     # cooldown pending → sibling
    assert float(out.utilities[0]) == 1.0
    now[0] = 6.0                                  # cooldown elapsed: one probe
    out = rs.invoke_batch(None, np.arange(2))
    assert float(out.utilities[0]) == 0.0         # probe succeeded on replica 0
    assert rs.tracker.healthy(0) and rs.n_available() == 2


def test_one_replica_outage_degrades_set_without_tripping_breaker(
        fitted_rb, agnews, pool):
    sets = [replicate_simulated(m, 2) for m in pool]
    sets[0].replicas[0] = FlakyMember(sets[0].replicas[0], fail_from=0)
    srv = _server(fitted_rb, sets, agnews, qps=30.0, budget_x=4.0)
    arrivals = poisson_arrivals(np.random.default_rng(6), 30.0, 8.0,
                                agnews.subset_indices("test"))
    stats = srv.run(arrivals)
    srv.close()
    assert all(br.state == CircuitState.CLOSED for br in srv.breakers)
    assert stats.n_completed == stats.n_submitted and stats.n_dropped == 0
    assert stats.n_reroutes == 0                  # absorbed inside the set
    assert sets[0].tracker.replicas[0].n_failures > 0
    assert not sets[0].tracker.healthy(0)         # dead replica ejected
    served_on = {r.model for r in srv.completed if not r.cache_hit}
    assert 0 in served_on                         # the member kept serving


# ---------------------------------------------------------------------------
# replica capacity caps
# ---------------------------------------------------------------------------

def test_greedy_schedule_window_respects_group_caps(fitted_rb, agnews):
    # the legacy safety-net semantics (cap_mode="defer"): over-cap groups are
    # deferred wholesale — what the server still applies to caps-unaware plans
    test = agnews.subset_indices("test")[:24]
    space = fitted_rb.candidate_space(test)
    budget = float(space.cost.max(axis=1).sum())  # rich: upgrades to b=1 states
    caps = {0: 1, 1: 1, 2: 1}
    res = greedy_schedule_window(space, test, budget, group_caps=caps,
                                 cap_mode="defer")
    per_model: dict = {}
    for state, _members in group_into_batches(res.assignment):
        per_model[state.model] = per_model.get(state.model, 0) + 1
    assert per_model and all(n <= caps[k] for k, n in per_model.items())
    assert len(res.deferred_idx) > 0              # the caps actually bound
    scheduled = set(res.assignment.query_idx.tolist())
    assert scheduled | set(res.deferred_idx.tolist()) == set(test.tolist())
    assert scheduled.isdisjoint(res.deferred_idx.tolist())
    # the capacity-aware walk (default cap_mode="pack") keeps the same cap
    # invariant but packs into wider batches, deferring strictly less
    packed = greedy_schedule_window(space, test, budget, group_caps=caps)
    per_model = {}
    for state, _members in group_into_batches(packed.assignment):
        per_model[state.model] = per_model.get(state.model, 0) + 1
    assert per_model and all(n <= caps[k] for k, n in per_model.items())
    assert len(packed.deferred_idx) < len(res.deferred_idx)
    assert packed.n_packed > 0
    sched = set(packed.assignment.query_idx.tolist())
    assert sched | set(packed.deferred_idx.tolist()) == set(test.tolist())
    assert sched.isdisjoint(packed.deferred_idx.tolist())


def test_group_cap_zero_removes_model_from_window_space(fitted_rb, agnews):
    test = agnews.subset_indices("test")[:16]
    space = fitted_rb.candidate_space(test)
    budget = float(space.cost[:, space.initial_state].sum()) * 4
    res = greedy_schedule_window(space, test, budget, group_caps={0: 0})
    assert 0 not in set(np.unique(res.assignment.model))
    # every member saturated: the window defers wholesale instead of crashing
    res = greedy_schedule_window(space, test, budget,
                                 group_caps={0: 0, 1: 0, 2: 0})
    assert len(res.assignment.query_idx) == 0
    assert res.deferred_idx.tolist() == test.tolist()


def test_server_never_dispatches_more_groups_than_replicas(
        fitted_rb, agnews, pool):
    sets = [replicate_simulated(m, 2) for m in pool]
    srv = _server(fitted_rb, sets, agnews, qps=40.0, budget_x=20.0,
                  window_s=0.5)
    arrivals = poisson_arrivals(np.random.default_rng(7), 40.0, 8.0,
                                agnews.subset_indices("test"))
    stats = srv.run(arrivals)
    srv.close()
    assert stats.n_completed == stats.n_submitted and stats.n_dropped == 0
    for w in stats.windows:
        for k in set(w.group_models):
            assert w.group_models.count(k) <= 2
    # capacity pressure engaged: the Δ-heap packed work into wider batches
    # (and/or held the unpackable remainder) instead of over-dispatching
    assert sum(w.n_capacity_held + w.n_cap_packed for w in stats.windows) > 0


# ---------------------------------------------------------------------------
# real-time pacing
# ---------------------------------------------------------------------------

def test_realtime_mode_paces_windows_on_a_fake_clock(fitted_rb, agnews, pool):
    test = agnews.subset_indices("test")
    clk = FakeClock()
    cfg = OnlineConfig(budget_per_s=_rate(fitted_rb, test, 20.0), window_s=0.25,
                       realtime=True)
    srv = OnlineRobatchServer(fitted_rb, pool, agnews, cfg, clock=clk)
    arrivals = poisson_arrivals(np.random.default_rng(8), 20.0, 3.0, test)
    stats = srv.run(arrivals)
    srv.close()
    assert stats.n_completed == stats.n_submitted
    # windows fired exactly on the boundaries: t = k·window_s, never late
    for k, w in enumerate(stats.windows, start=1):
        assert w.t == pytest.approx(k * 0.25)
        assert w.late_s == 0.0
    assert clk.t == pytest.approx(len(stats.windows) * 0.25)
    assert clk.n_sleeps >= len(stats.windows)


def test_realtime_run_tracks_wall_clock_duration(fitted_rb, agnews, pool):
    test = agnews.subset_indices("test")
    cfg = OnlineConfig(budget_per_s=_rate(fitted_rb, test, 20.0), window_s=0.1,
                       realtime=True)
    srv = OnlineRobatchServer(fitted_rb, pool, agnews, cfg)  # monotonic clock
    arrivals = poisson_arrivals(np.random.default_rng(8), 20.0, 0.5, test)
    t0 = time.monotonic()
    stats = srv.run(arrivals)
    wall = time.monotonic() - t0
    srv.close()
    assert stats.n_completed == stats.n_submitted
    assert 0.4 <= wall <= 3.0          # paced: neither instant nor runaway


def test_virtual_and_realtime_replay_one_seeded_stream_identically(
        fitted_rb, agnews, pool):
    test = agnews.subset_indices("test")
    arrivals = poisson_arrivals(np.random.default_rng(11), 25.0, 5.0, test,
                                repeat_frac=0.3)

    def serve(realtime):
        cfg = OnlineConfig(budget_per_s=_rate(fitted_rb, test, 25.0),
                           window_s=0.25, realtime=realtime)
        srv = OnlineRobatchServer(fitted_rb, pool, agnews, cfg,
                                  clock=FakeClock() if realtime else None)
        stats = srv.run(arrivals)
        srv.close()
        trace = sorted((r.rid, r.query_idx, r.model, r.batch, r.cache_hit,
                        round(r.cost, 12), round(r.completed_at, 9))
                       for r in srv.completed)
        return stats, trace

    v_stats, v_trace = serve(realtime=False)
    r_stats, r_trace = serve(realtime=True)
    assert v_trace == r_trace
    assert v_stats.total_cost == pytest.approx(r_stats.total_cost)
    assert v_stats.qps == pytest.approx(r_stats.qps)


class _SlowMember:
    """A simulated member whose every group takes ``service_s`` of wall
    time, and says so in its own ``latency_s``."""

    def __init__(self, inner, service_s):
        self.inner, self.service_s = inner, service_s

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def invoke_batch(self, wl, batch_idx, **kw):
        time.sleep(self.service_s)
        out = self.inner.invoke_batch(wl, batch_idx)
        out.latency_s = self.service_s
        return out


def test_realtime_completion_counts_the_wait_behind_earlier_groups(
        fitted_rb, agnews, pool):
    """One dispatch thread serves a round's groups one after another: on the
    wall clock a later group completes after the earlier groups' service,
    which its own latency_s leaves out.  The stamps split the wait."""
    test = agnews.subset_indices("test")
    service = 0.05
    cfg = OnlineConfig(budget_per_s=_rate(fitted_rb, test, 100.0), window_s=0.25,
                       realtime=True, max_workers=1)
    srv = OnlineRobatchServer(fitted_rb, [_SlowMember(m, service) for m in pool],
                              agnews, cfg)
    srv.run([(0.01, int(q)) for q in test[:40]])
    srv.close()
    rep = next(w for w in srv.windows if w.n_groups >= 2)
    done = [r for r in srv.completed if r.admitted_at == rep.t]
    assert len({r.served_at for r in done}) == rep.n_groups
    for r in done:
        assert r.admitted_at - r.arrived_at == pytest.approx(rep.t - 0.01)
        assert r.completed_at >= r.served_at + service
    # the last group began after every other group's service
    assert max(r.served_at for r in done) >= rep.t + service * (rep.n_groups - 1)
    assert max(r.completed_at for r in done) >= rep.t + service * rep.n_groups


def test_run_live_submits_the_stream_from_a_pacer_thread(
        fitted_rb, agnews, pool):
    test = agnews.subset_indices("test")
    cfg = OnlineConfig(budget_per_s=_rate(fitted_rb, test, 30.0), window_s=0.1,
                       realtime=True)
    srv = OnlineRobatchServer(fitted_rb, pool, agnews, cfg)
    arrivals = poisson_arrivals(np.random.default_rng(12), 30.0, 0.5, test)
    stats = srv.run_live(arrivals, duration_s=0.5)
    srv.close()
    assert stats.n_submitted == len(arrivals)
    assert stats.n_completed == stats.n_submitted
    # the pacer stamped each request with its generated arrival time
    assert sorted(r.arrived_at for r in srv.completed) == \
        pytest.approx(sorted(t for t, _ in arrivals))


def test_run_live_flags_a_leaked_pacer_thread(fitted_rb, agnews, pool):
    # a pacer that ignores stop() past the join timeout must be surfaced as
    # pacer_leaked=True (and warned), not silently abandoned via the daemon
    # flag; a clean shutdown reads False
    import repro.serving.online as online_mod

    test = agnews.subset_indices("test")
    cfg = OnlineConfig(budget_per_s=_rate(fitted_rb, test, 30.0), window_s=0.1,
                       realtime=True)
    srv = OnlineRobatchServer(fitted_rb, pool, agnews, cfg)
    arrivals = [(0.05, int(test[0]))]
    srv.run_live(arrivals, duration_s=0.1)
    assert srv.pacer_leaked is False

    class _Stubborn(online_mod.LiveArrivalSource):
        def join(self, timeout=None):
            return None                      # never actually exits

        def is_alive(self):
            # alive only to the shutdown path: the serving loop's drain
            # check (pre-stop) must still see the stream as finished
            return self._stop_requested.is_set()

    srv2 = OnlineRobatchServer(fitted_rb, pool, agnews, cfg)
    real = online_mod.LiveArrivalSource
    online_mod.LiveArrivalSource = _Stubborn
    try:
        srv2.run_live(arrivals, duration_s=0.1, join_timeout_s=0.05)
    finally:
        online_mod.LiveArrivalSource = real
        srv2.close()
    srv.close()
    assert srv2.pacer_leaked is True


# ---------------------------------------------------------------------------
# chaos injection: seeded determinism + the dispatch-hardening ladder
# ---------------------------------------------------------------------------

from repro.serving.fault import ChaosMember, CircuitBreaker, ReplicaTracker  # noqa: E402
from repro.serving.pool import DispatchTimeout  # noqa: E402


def test_chaos_member_is_deterministic_and_counts_faults():
    def mk():
        return ChaosMember(_FakeMember(1.0), seed=42, latency_noise_s=0.05,
                           fail_from=2, fail_until=4, error_rate=1.0)

    traces = []
    for c in (mk(), mk()):
        lats = []
        for _ in range(6):
            try:
                lats.append(c.invoke_batch(None, np.arange(2)).latency_s)
            except RuntimeError:
                lats.append(None)
        traces.append(lats)
        assert c.n_calls == 6 and c.n_faults == 2 and c.n_hangs == 0
    assert traces[0] == traces[1]            # bit-identical given the seed
    assert traces[0][2] is None and traces[0][3] is None
    assert all(lat > 0.01 for i, lat in enumerate(traces[0])
               if i not in (2, 3))           # noise added on surviving calls


def test_chaos_member_slow_degrade_grows_latency():
    c = ChaosMember(_FakeMember(1.0), seed=0, degrade_s=0.1)
    lats = [c.invoke_batch(None, np.arange(1)).latency_s for _ in range(4)]
    assert lats == sorted(lats)
    assert lats[3] - lats[0] == pytest.approx(0.3)


def test_chaos_member_proxies_the_member_protocol():
    inner = _FakeMember(1.0)
    c = ChaosMember(inner, seed=0)
    assert (c.name, c.c_in, c.c_out, c.context_len) == \
        (inner.name, inner.c_in, inner.c_out, inner.context_len)
    assert c.supports_streams is False and c.supports_generation is False


def test_dispatch_timeout_fails_over_from_hung_replica():
    hung = ChaosMember(_FakeMember(0.0), seed=1, hang_from=0, hang_until=1,
                       hang_s=5.0)
    rs = ReplicaSet([hung, _FakeMember(1.0)], name="m", dispatch_timeout_s=0.2)
    t0 = time.perf_counter()
    out = rs.invoke_batch(None, np.arange(2))
    wall = time.perf_counter() - t0
    assert float(out.utilities[0]) == 1.0    # sibling served the batch
    assert wall < 4.0                        # did not wait out the hang
    assert rs.n_timeouts == 1 and hung.n_hangs == 1
    assert rs.tracker.replicas[0].n_failures == 1
    assert rs.loads() == [0, 0]              # in-flight slots fully released


def test_dispatch_timeout_raises_when_every_replica_hangs():
    rs = ReplicaSet([ChaosMember(_FakeMember(0.0), seed=2, hang_from=0,
                                 hang_s=5.0)],
                    name="m", dispatch_timeout_s=0.1)
    with pytest.raises(RuntimeError, match="all 1 replicas"):
        rs.invoke_batch(None, np.arange(2))
    assert rs.n_timeouts == 1


def test_dispatch_retry_ladder_recovers_transient_fault():
    flaky = FlakyMember(_FakeMember(1.0), fail_from=0, fail_until=2)
    rs = ReplicaSet([flaky], name="m", max_dispatch_retries=2,
                    backoff_base_s=0.01, backoff_cap_s=0.02)
    t0 = time.perf_counter()
    out = rs.invoke_batch(None, np.arange(2))
    assert float(out.utilities[0]) == 1.0    # 3rd attempt, SAME replica
    assert time.perf_counter() - t0 >= 0.02  # 0.01 + 0.02 backoff slept
    assert rs.n_dispatch_retries == 2
    assert rs.tracker.replicas[0].n_failures == 2
    assert rs.tracker.healthy(0)             # success reset the streak
    assert rs.loads() == [0]


def test_timeouts_never_burn_same_replica_retries():
    hung = ChaosMember(_FakeMember(0.0), seed=3, hang_from=0, hang_until=10,
                       hang_s=5.0)
    rs = ReplicaSet([hung, _FakeMember(1.0)], name="m",
                    dispatch_timeout_s=0.1, max_dispatch_retries=3)
    out = rs.invoke_batch(None, np.arange(2))
    assert float(out.utilities[0]) == 1.0
    assert hung.n_calls == 1                 # one dispatch, zero retries on it
    assert rs.n_dispatch_retries == 0


def test_dispatch_timeout_error_is_typed():
    with pytest.raises(DispatchTimeout):
        rs = ReplicaSet([ChaosMember(_FakeMember(0.0), seed=4, hang_from=0,
                                     hang_s=5.0)],
                        name="m", dispatch_timeout_s=0.05)
        try:
            rs.invoke_batch(None, np.arange(1))
        except RuntimeError as e:
            raise e.__cause__                # the failover chain keeps it


# ---------------------------------------------------------------------------
# concurrency: breaker half-open probes and tracker ejection under racing
# dispatch threads
# ---------------------------------------------------------------------------

def test_breaker_half_open_concurrent_probes_and_single_retrip():
    now = [0.0]
    br = CircuitBreaker(BreakerPolicy(failure_threshold=1, recovery_time_s=1.0),
                        clock=lambda: now[0])
    br.record_failure()
    assert br.state is CircuitState.OPEN and br.n_trips == 1
    assert not br.allow_request()            # cooling down
    now[0] = 2.0
    barrier = threading.Barrier(8)
    got = []

    def probe():
        barrier.wait()
        got.append(br.allow_request())

    ths = [threading.Thread(target=probe) for _ in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=10.0)
    assert got == [True] * 8                 # racing probes all admitted
    assert br.state is CircuitState.HALF_OPEN
    br.record_failure()                      # the probe failed
    assert br.state is CircuitState.OPEN and br.n_trips == 2
    now[0] = 4.0
    assert br.allow_request()
    br.record_success()
    assert br.state is CircuitState.CLOSED and br.failure_count == 0


def test_concurrent_dispatch_ejects_dead_replica_and_drains_slots():
    dead = FlakyMember(_FakeMember(0.0), fail_from=0)    # always faults
    rs = ReplicaSet([dead, _FakeMember(1.0), _FakeMember(2.0)], name="m")
    outs: list = []
    barrier = threading.Barrier(12)

    def work():
        barrier.wait()
        outs.append(float(rs.invoke_batch(None, np.arange(2)).utilities[0]))

    ths = [threading.Thread(target=work) for _ in range(12)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=10.0)
    assert len(outs) == 12
    assert all(u in (1.0, 2.0) for u in outs)   # nothing served by the corpse
    assert not rs.tracker.healthy(0)            # racing failures ejected it
    assert rs.tracker.replicas[0].n_ejections >= 1
    assert rs.loads() == [0, 0, 0]              # every in-flight slot released


def test_tracker_concurrent_failures_eject_exactly_not_forever():
    trk = ReplicaTracker(2, ReplicaPolicy(eject_after=4, cooldown_s=30.0),
                         clock=lambda: 0.0)
    barrier = threading.Barrier(8)

    def fail():
        barrier.wait()
        trk.record_failure(0)

    ths = [threading.Thread(target=fail) for _ in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=10.0)
    assert not trk.healthy(0) and trk.healthy(1)
    assert trk.replicas[0].n_failures >= trk.policy.eject_after
    trk.record_success(0)                       # re-admission clears the slate
    assert trk.healthy(0) and trk.replicas[0].consecutive_failures == 0
