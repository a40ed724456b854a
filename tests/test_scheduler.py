"""Scheduler properties: Eq. 6/7 constraints, dominance-pruning losslessness
(Thm. 5.3), greedy vs brute-force, budget monotonicity (hypothesis-driven)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.pareto import CandidateSpace, pareto_frontier
from repro.core.problem import State
from repro.core.scheduler import brute_force_schedule, greedy_schedule


# ---------------------------------------------------------------------------
# synthetic candidate spaces (no pool needed: scheduler is pure)
# ---------------------------------------------------------------------------

def random_space(rng: np.random.Generator, n: int, n_models: int, n_batches: int) -> CandidateSpace:
    """States (k, b) with cost increasing in k and decreasing in b; utilities
    arbitrary in [0,1] — the scheduler must cope with any proxy model."""
    batches = [1, 2, 4][:n_batches]
    states, cost_cols, util_cols = [], [], []
    base = rng.uniform(0.5, 2.0, size=(n, n_models)).cumsum(axis=1)  # asc in k
    sys_c = rng.uniform(0.5, 3.0, size=n_models).cumsum()            # asc in k
    for k in range(n_models):
        for b in batches:
            states.append(State(k, b))
            cost_cols.append(base[:, k] + sys_c[k] / b)
            util_cols.append(rng.uniform(0, 1, size=n))
    init = states.index(State(0, batches[-1]))
    return CandidateSpace(states=states, cost=np.stack(cost_cols, 1),
                          util=np.stack(util_cols, 1), initial_state=init)


space_params = st.tuples(
    st.integers(1, 6),       # queries
    st.integers(1, 3),       # models
    st.integers(1, 3),       # batch sizes
    st.integers(0, 10_000),  # seed
    st.floats(0.0, 3.0),     # budget slack multiplier
)


def _budget_for(space, slack):
    init = space.cost[:, space.initial_state].sum()
    max_c = space.cost.max(axis=1).sum()
    return init + slack * (max_c - init)


@settings(max_examples=120, deadline=None)
@given(space_params)
def test_each_query_exactly_one_state(params):
    n, k, nb, seed, slack = params
    space = random_space(np.random.default_rng(seed), n, k, nb)
    res = greedy_schedule(space, np.arange(n), _budget_for(space, slack))
    assert len(res.assignment.model) == n          # Eq. 6
    for s in res.assignment.states():
        assert s in space.states


@settings(max_examples=120, deadline=None)
@given(space_params)
def test_budget_respected(params):
    n, k, nb, seed, slack = params
    space = random_space(np.random.default_rng(seed), n, k, nb)
    budget = _budget_for(space, slack)
    res = greedy_schedule(space, np.arange(n), budget)
    if not res.infeasible:
        assert res.amortized_cost <= budget + 1e-9  # Eq. 7 (amortized accounting)


@settings(max_examples=120, deadline=None)
@given(space_params)
def test_utility_at_least_initial(params):
    n, k, nb, seed, slack = params
    space = random_space(np.random.default_rng(seed), n, k, nb)
    res = greedy_schedule(space, np.arange(n), _budget_for(space, slack))
    init_u = space.util[:, space.initial_state].sum()
    assert res.est_utility >= init_u - 1e-9


@settings(max_examples=60, deadline=None)
@given(st.tuples(st.integers(1, 5), st.integers(1, 3), st.integers(0, 5_000)))
def test_budget_monotonicity_endpoints(params):
    """Hypothesis finding: Alg. 1 is NOT pointwise budget-monotone — a larger
    budget can afford an early high-Δ expensive upgrade that crowds out
    several cheaper ones.  What IS guaranteed: an all-affordable budget yields
    the frontier maximum (≥ any intermediate outcome), and the minimum budget
    yields the initial assignment (≤ any other)."""
    n, k, seed = params
    space = random_space(np.random.default_rng(seed), n, k, 2)
    budgets = np.linspace(_budget_for(space, 0), _budget_for(space, 1.2), 6)
    utils = [greedy_schedule(space, np.arange(n), b).est_utility for b in budgets]
    assert utils[-1] >= max(utils) - 1e-9     # saturated budget = frontier max
    assert utils[0] <= min(utils) + 1e-9      # starved budget = initial only


@settings(max_examples=60, deadline=None)
@given(st.tuples(st.integers(1, 5), st.integers(1, 2), st.integers(0, 5_000),
                 st.floats(0.1, 1.5)))
def test_greedy_never_exceeds_optimum(params):
    """Sanity: greedy ≤ brute-force optimum, ≥ the initial assignment."""
    n, k, seed, slack = params
    space = random_space(np.random.default_rng(seed), n, k, 2)
    budget = _budget_for(space, slack)
    g = greedy_schedule(space, np.arange(n), budget)
    bf = brute_force_schedule(space, np.arange(n), budget)
    assert g.est_utility <= bf.est_utility + 1e-9
    assert g.est_utility >= space.util[:, space.initial_state].sum() - 1e-9


def test_greedy_quality_statistical():
    """Δ-ratio greedy has NO adversarial constant-factor guarantee (hypothesis
    finds <0.5× instances: a high-Δ unaffordable transition is dropped, Alg. 1
    line 11-12).  The paper's quality claim is empirical — check it
    statistically: mean ≥ 90% of optimal over random micro instances."""
    rng = np.random.default_rng(0)
    ratios = []
    for seed in range(60):
        space = random_space(np.random.default_rng(seed), 5, 2, 2)
        budget = _budget_for(space, float(rng.uniform(0.2, 1.2)))
        g = greedy_schedule(space, np.arange(5), budget)
        bf = brute_force_schedule(space, np.arange(5), budget)
        if bf.est_utility > 0:
            ratios.append(g.est_utility / bf.est_utility)
    assert np.mean(ratios) >= 0.90, np.mean(ratios)
    assert np.min(ratios) >= 0.40, np.min(ratios)


def test_pareto_pruning_lossless():
    """Thm. 5.3: scheduling over pruned frontiers equals scheduling over the
    frontier plus dominated states (we add dominated states and check the
    greedy objective is unchanged)."""
    rng = np.random.default_rng(7)
    n = 6
    space = random_space(rng, n, 3, 3)
    budget = _budget_for(space, 0.7)
    base = greedy_schedule(space, np.arange(n), budget)

    # append strictly dominated copies of every state (more cost, less utility)
    states2 = space.states + [State(s.model, s.batch) for s in space.states]
    cost2 = np.concatenate([space.cost, space.cost + 1.0], axis=1)
    util2 = np.concatenate([space.util, np.clip(space.util - 0.1, 0, 1)], axis=1)
    space2 = CandidateSpace(states=states2, cost=cost2, util=util2,
                            initial_state=space.initial_state)
    withdom = greedy_schedule(space2, np.arange(n), budget)
    assert withdom.est_utility == pytest.approx(base.est_utility)


def test_pareto_frontier_sorted_and_nondominated():
    rng = np.random.default_rng(3)
    cost = rng.uniform(0, 1, 50)
    util = rng.uniform(0, 1, 50)
    fr = pareto_frontier(cost, util)
    assert np.all(np.diff(cost[fr]) >= 0)
    assert np.all(np.diff(util[fr]) > 0)
    # no dominating pair outside the frontier
    for j in range(50):
        dominated = ((cost[fr] <= cost[j]) & (util[fr] >= util[j])).any()
        assert dominated or j in fr


def test_unaffordable_upgrade_dropped_not_fatal():
    """Alg. 1 line 11–12: a too-expensive top-Δ upgrade is skipped and the
    scheduler keeps upgrading other queries."""
    states = [State(0, 2), State(0, 1), State(1, 1)]
    cost = np.array([[1.0, 2.0, 100.0],     # q0: huge second upgrade
                     [1.0, 1.5, 2.0]])
    util = np.array([[0.1, 0.2, 1.0],
                     [0.1, 0.3, 0.9]])
    space = CandidateSpace(states=states, cost=cost, util=util, initial_state=0)
    res = greedy_schedule(space, np.arange(2), budget=2.0 + 4.0)
    # q0 can afford (0,1)->cost2; q1 can reach (1,1)->cost2
    assert res.est_utility >= 0.2 + 0.9 - 1e-9


# ---------------------------------------------------------------------------
# vectorized scheduler (beyond-paper): parity + constraints
# ---------------------------------------------------------------------------

from repro.core.scheduler import greedy_schedule_vectorized


@settings(max_examples=60, deadline=None)
@given(space_params)
def test_vectorized_matches_heap_objective(params):
    n, k, nb, seed, slack = params
    space = random_space(np.random.default_rng(seed), n, k, nb)
    budget = _budget_for(space, slack)
    heap = greedy_schedule(space, np.arange(n), budget)
    vec = greedy_schedule_vectorized(space, np.arange(n), budget)
    if not vec.infeasible:
        assert vec.amortized_cost <= budget + 1e-9
    # round-commit ordering can differ from the global heap on adversarial
    # micro instances; require ≥85% of the heap objective and never below the
    # initial assignment (empirical parity on real workloads is measured in
    # benchmarks/fig11 and is ≈1.0)
    init_u = space.util[:, space.initial_state].sum()
    assert vec.est_utility >= max(0.85 * heap.est_utility, init_u) - 1e-9


def test_vectorized_each_query_one_state():
    rng = np.random.default_rng(11)
    space = random_space(rng, 20, 3, 3)
    res = greedy_schedule_vectorized(space, np.arange(20), _budget_for(space, 0.8))
    assert len(res.assignment.model) == 20
    for s in res.assignment.states():
        assert s in space.states


# ---------------------------------------------------------------------------
# uncertainty-robust walk: λ·σ-penalized gains, worst-case budget margin
# ---------------------------------------------------------------------------

from repro.core.scheduler import (  # noqa: E402
    greedy_schedule_window,
    restrict_space,
    take_rows,
)


def random_space_with_sigma(rng, n, n_models, n_batches):
    space = random_space(rng, n, n_models, n_batches)
    return CandidateSpace(states=space.states, cost=space.cost,
                          util=space.util, initial_state=space.initial_state,
                          sigma=rng.uniform(0.0, 0.4, size=space.util.shape))


@settings(max_examples=80, deadline=None)
@given(space_params)
def test_robust_at_zero_is_bit_identical(params):
    # the λ=0 / margin=0 path must return EXACTLY the point-estimate walk —
    # same assignment, same floats — even when sigma is present
    n, k, nb, seed, slack = params
    rng = np.random.default_rng(seed)
    space = random_space_with_sigma(rng, n, k, nb)
    budget = _budget_for(space, slack)
    base = greedy_schedule(space, np.arange(n), budget)
    zero = greedy_schedule(space, np.arange(n), budget,
                           robust_lambda=0.0, cost_margin=0.0)
    assert np.array_equal(zero.assignment.model, base.assignment.model)
    assert np.array_equal(zero.assignment.batch, base.assignment.batch)
    assert zero.est_utility == base.est_utility
    assert zero.amortized_cost == base.amortized_cost
    assert zero.spent_budget == base.spent_budget
    caps = {m: n for m in range(k)}
    wbase = greedy_schedule_window(space, np.arange(n), budget, group_caps=caps)
    wzero = greedy_schedule_window(space, np.arange(n), budget, group_caps=caps,
                                   robust_lambda=0.0, cost_margin=0.0)
    assert np.array_equal(wzero.assignment.model, wbase.assignment.model)
    assert wzero.est_utility == wbase.est_utility
    assert wzero.spent_budget == wbase.spent_budget


def _three_state_space(sigma):
    # one query; an expensive high-û/high-σ upgrade vs an equally priced
    # lower-û/zero-σ one — Pareto pruning keeps only the better walk-utility
    states = [State(0, 1), State(1, 1), State(2, 1)]
    return CandidateSpace(states=states,
                          cost=np.array([[1.0, 2.0, 2.0]]),
                          util=np.array([[0.5, 0.9, 0.85]]),
                          initial_state=0,
                          sigma=np.array([sigma]))


def test_robust_lambda_switches_to_low_sigma_upgrade():
    space = _three_state_space([0.0, 0.3, 0.0])
    idx = np.arange(1)
    base = greedy_schedule(space, idx, budget=2.5)
    assert int(base.assignment.model[0]) == 1          # û says model 1
    rob = greedy_schedule(space, idx, budget=2.5, robust_lambda=1.0)
    assert int(rob.assignment.model[0]) == 2           # û−λσ says model 2
    # accounting stays in raw point-estimate currency
    assert rob.est_utility == pytest.approx(0.85)
    assert rob.amortized_cost == pytest.approx(2.0)


def test_cost_margin_blocks_worst_case_budget_overrun():
    space = _three_state_space([0.0, 0.0, 0.0])
    idx = np.arange(1)
    base = greedy_schedule(space, idx, budget=2.8)
    assert int(base.assignment.model[0]) == 1          # affordable point-est.
    marg = greedy_schedule(space, idx, budget=2.8, cost_margin=0.5)
    assert int(marg.assignment.model[0]) == 0          # 2.0·1.5 > 2.8: held
    # the walk drew the worst-case price of what it DID commit
    assert marg.spent_budget == pytest.approx(1.0 * 1.5)
    assert marg.amortized_cost == pytest.approx(1.0)


def test_robust_schedule_fits_worst_case_inside_budget():
    rng = np.random.default_rng(7)
    space = random_space_with_sigma(rng, 24, 3, 3)
    budget = _budget_for(space, 0.6)
    for margin in (0.1, 0.25, 0.5):
        res = greedy_schedule(space, np.arange(24), budget, cost_margin=margin)
        if not res.infeasible:
            assert res.amortized_cost * (1 + margin) <= budget + 1e-9
            assert res.spent_budget == pytest.approx(
                res.amortized_cost * (1 + margin))


def test_sigma_survives_restrict_and_take_rows():
    rng = np.random.default_rng(3)
    space = random_space_with_sigma(rng, 8, 3, 2)
    sub = restrict_space(space, {0, 2})
    assert sub.sigma is not None and sub.sigma.shape == sub.util.shape
    assert all(s.model != 1 for s in sub.states)
    rows = take_rows(sub, np.array([1, 3, 5]))
    assert rows.sigma is not None and rows.sigma.shape == rows.util.shape
    np.testing.assert_array_equal(rows.sigma, sub.sigma[[1, 3, 5]])


def test_fitted_candidate_space_carries_calibration_sigma(fitted_rb, agnews):
    test = agnews.subset_indices("test")[:16]
    space = fitted_rb.candidate_space(test)
    assert space.sigma is not None
    assert space.sigma.shape == space.util.shape
    assert np.all(space.sigma >= 0)
    assert float(space.sigma.max()) > 0          # residual spread is real
    # sigma is constant per (model, batch) column: it comes from the
    # calibration's per-batch residual std, not per-query noise
    assert np.allclose(space.sigma, space.sigma[:1, :])


def test_robust_policy_params_flow_and_validate(fitted_rb, agnews, pool):
    from repro.api.policies import RobatchPolicy

    with pytest.raises(ValueError, match="robust"):
        RobatchPolicy(robust=-0.1)
    with pytest.raises(ValueError, match="cost_margin"):
        RobatchPolicy(cost_margin=-1.0)
    test = agnews.subset_indices("test")[:32]
    space = fitted_rb.candidate_space(test)
    budget = float(space.cost[:, space.initial_state].sum()) * 2.0
    plain = RobatchPolicy().fit(pool, agnews, artifacts=fitted_rb)
    robust = RobatchPolicy(robust=0.0, cost_margin=0.0).fit(
        pool, agnews, artifacts=fitted_rb)
    a = plain.plan_window(space, test, budget)
    b = robust.plan_window(space, test, budget)
    assert np.array_equal(a.schedule.assignment.model,
                          b.schedule.assignment.model)
    assert a.est_utility == b.est_utility
    # a margin policy never schedules past its worst-case budget
    guarded = RobatchPolicy(cost_margin=0.25).fit(pool, agnews,
                                                  artifacts=fitted_rb)
    c = guarded.plan_window(space, test, budget)
    assert c.est_cost * 1.25 <= budget + 1e-9
