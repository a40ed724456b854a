"""Thm. 3.2: the Maximum-Coverage reduction.

We build the paper's reduction instance from random MC instances and check
that the optimal Route-with-Batching objective equals the optimal coverage
(both solved by brute force on micro instances) — validating that the
constructed routing instance is exactly as hard as MC.
"""
import itertools

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.pareto import CandidateSpace
from repro.core.problem import State


def mc_brute_force(sets: list[set], budget: int) -> int:
    n_elems = len(set().union(*sets)) if sets else 0
    best = 0
    for chosen in itertools.combinations(range(len(sets)), min(budget, len(sets))):
        covered = set().union(*(sets[k] for k in chosen)) if chosen else set()
        best = max(best, len(covered))
    return best


def reduction_space(sets: list[set], n: int) -> CandidateSpace:
    """The Thm. 3.2 construction: B_k = {n}, C_sys = 1, C_q = 0,
    u_{i,k,n} = 1 iff e_i ∈ T_k."""
    K = len(sets)
    states = [State(k, n) for k in range(K)]
    cost = np.zeros((n, K))     # per-query amortized cost = C_sys/n; see below
    util = np.zeros((n, K))
    for k, T in enumerate(sets):
        cost[:, k] = 1.0 / n     # C_sys(m_k)/b with C_sys=1, b=n
        for e in T:
            util[e, k] = 1.0
    return CandidateSpace(states=states, cost=cost, util=util, initial_state=0)


def routing_brute_force(space: CandidateSpace, n: int, budget: float) -> float:
    """Exact optimum of the constructed instance under Eq. 4 accounting:
    cost = number of *used* models (each used model serves ≤ n queries in one
    invocation of batch size n)."""
    K = len(space.states)
    best = 0.0
    for r in range(0, min(K, int(budget)) + 1):
        for used in itertools.combinations(range(K), r):
            u = space.util[:, list(used)].max(axis=1).sum() if used else 0.0
            best = max(best, u)
    return best


@settings(max_examples=40, deadline=None)
@given(st.integers(2, 5), st.integers(2, 5), st.integers(1, 3), st.integers(0, 10_000))
def test_reduction_equivalence(K, n, B, seed):
    rng = np.random.default_rng(seed)
    sets = []
    for _ in range(K):
        members = set(int(i) for i in np.where(rng.uniform(size=n) < 0.5)[0])
        if not members:
            members = {int(rng.integers(n))}
        sets.append(members)
    covered_all = set().union(*sets)
    # restrict universe to covered elements (paper: elements = ∪ T_k)
    mc_opt = mc_brute_force(sets, B)
    space = reduction_space(sets, n)
    route_opt = routing_brute_force(space, n, float(B))
    assert route_opt == pytest.approx(mc_opt)


def test_reduction_cost_counts_used_models():
    """C(m_k, n) = ceil(N_k / n) = 1 iff the model is used: total cost equals
    the number of used models, as the proof sketch argues."""
    sets = [{0, 1}, {2}]
    n = 3
    # model 0 serves {0,1}: ceil(2/3)=1; model 1 serves {2}: ceil(1/3)=1
    assert int(np.ceil(2 / n)) == 1 and int(np.ceil(1 / n)) == 1
