import glob
import json
import os

import numpy as np
import pytest

import tiny
import traffic

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))


def load(path):
    with open(path) as f:
        return json.load(f)


MIXES = {os.path.basename(p)[:-5]: load(p)
         for p in sorted(glob.glob(os.path.join(CHIP, "traffic", "*.json")))}
MIXES["tiny"] = tiny.MIX
OPEN = {k: m for k, m in MIXES.items() if m["load"]["loop"] == "open"}


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_traffic(name):
    mix = MIXES[name]
    a, b = traffic.generate(mix, 2**33 + 1, 10), traffic.generate(mix, 2**33 + 1, 10)
    assert a.queries == b.queries and a.system_prompt == b.system_prompt
    np.testing.assert_array_equal(a.order, b.order)
    np.testing.assert_array_equal(a.due_s, b.due_s)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_seeds_share_the_sizes_in_another_order(name):
    mix = MIXES[name]
    a, b = traffic.generate(mix, 1, 10), traffic.generate(mix, 2, 10)
    np.testing.assert_array_equal(np.sort(a.in_tokens), np.sort(b.in_tokens))
    assert not np.array_equal(a.in_tokens, b.in_tokens)
    assert a.queries != b.queries
    assert len(a.due_s) == len(b.due_s)
    if name in OPEN:
        assert not np.array_equal(a.due_s, b.due_s)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_texts_distinct_and_sized_as_billed(name):
    mix = MIXES[name]
    t = traffic.generate(mix, 7, 10)
    assert len(set(t.queries)) == len(t.queries)
    assert all(len(q.encode()) + traffic.QUERY_PREFIX_BYTES == k
               for q, k in zip(t.queries, t.in_tokens))
    assert len(t.system_prompt.encode()) + 1 == mix["task"]["sys_tokens"]
    assert max(t.in_tokens) <= mix["query_tokens_max"]
    assert all(q == q.strip() and ";" not in q for q in t.queries)


@pytest.mark.parametrize("name", sorted(OPEN))
def test_open_loop_rate(name):
    mix = OPEN[name]
    t = traffic.generate(mix, 3, 40)
    assert len(t.due_s) == round(40 * mix["load"]["rate_qps"])
    assert (np.diff(t.due_s) >= 0).all() and t.due_s[-1] < 40


def test_splits_disjoint_and_order_from_test():
    t = traffic.generate(MIXES["agnews.closed"], 11, 10)
    parts = [set(v.tolist()) for v in t.split.values()]
    assert sum(map(len, parts)) == len(set().union(*parts)) == len(t.queries)
    assert set(t.order.tolist()) == parts[2]


def test_prompt_bounds_fit_the_engine():
    """Each cell's longest batch prompt and its answers fit its engine, at
    the widest batch its task is served at (4 under MMLU's half budget, 8 as
    a margin for the unbudgeted AG News)."""
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    configs = {c["name"]: load(os.path.join(ROOT, c["file"])) for c in bench["configs"]}
    for cell in bench["workloads"]:
        mix = MIXES[cell["traffic"]]
        max_len = configs[cell["config"]]["serving"]["max_len"]
        widest = {"mmlu": 4, "agnews": 8}[mix["task"]["name"]]
        lo, hi = traffic.prompt_bounds(mix, [1, widest])
        answers = (mix["answer_tokens_per_query"] + 1) * widest
        assert hi + answers <= max_len - 1
