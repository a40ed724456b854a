"""The knee sweep, off the chip at a tiny size: every run prints the line its
docstring names, and the last line the knee read from them."""
import json

import jax
import pytest

import run
import sweep
import tiny

LINE_KEYS = {"offered_qps", "seed", "answered_qps", "latency_p95_ms", "usd_per_kquery",
             "queries_per_prompt", "attempted", "failed", "correct", "memory_peak_bytes",
             "logit_gap_mean"}


@pytest.fixture(autouse=True)
def tiny_cell(monkeypatch):
    """The tiny cell in place of BENCHMARK.json's, and everything of a run
    but its look for a chip."""
    def devices(chips):
        devs = jax.devices()
        return devs, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                      "count": len(devs)}, None

    monkeypatch.setattr(run, "require_chips", devices)
    monkeypatch.setattr(run, "resolve_cell", lambda name: (tiny.CELL, tiny.CONFIG, tiny.MIX))
    monkeypatch.setattr(run, "set_compile_cache", lambda: None)
    tiny.small_modeling_stage(monkeypatch)


def test_sweep_prints_each_run_and_the_knee(capsys):
    assert sweep.main(["--workload", "tiny.open", "--rates", "4,8", "--seconds", "2",
                       "--seeds", str(2**33 + 7)]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    runs, summary = lines[:-1], lines[-1]
    assert [ln["offered_qps"] for ln in runs] == [4.0, 8.0]
    assert [ln["seed"] for ln in runs] == [2**33 + 7, 2**33 + 8]
    for ln in runs:
        assert set(ln) == LINE_KEYS
        assert ln["correct"] and ln["failed"] == 0
        assert ln["logit_gap_mean"] <= tiny.MIX["limits"]["logit_gap_mean"]
    assert summary == dict(sweep.knee(runs), workload="tiny.open")


@pytest.mark.parametrize("answered, p95, expect", [
    ((4.0, 8.0, 12.0), (100, 150, 190), 12.0),      # keeps up everywhere
    ((4.0, 8.0, 10.0), (100, 150, 190), 8.0),       # falls behind at 12
    ((4.0, 8.0, 12.0), (100, 150, 250), 8.0),       # tail past twice the lowest rate's
    ((4.0, 7.0, 12.0), (100, 150, 190), 4.0),       # a rate that falls behind ends it
    ((3.0, 8.0, 12.0), (100, 150, 190), None),      # the lowest rate falls behind: no knee
])
def test_knee_follows_the_docstring(answered, p95, expect):
    lines = [{"offered_qps": r, "answered_qps": a, "latency_p95_ms": p}
             for r, a, p in zip((4.0, 8.0, 12.0), answered, p95)]
    k = sweep.knee(lines)
    assert k["knee_qps"] == expect
    assert k["rate_qps"] == (None if expect is None else pytest.approx(0.8 * expect))


def test_knee_takes_the_worst_seed():
    lines = [{"offered_qps": 8.0, "answered_qps": 8.0, "latency_p95_ms": 100},
             {"offered_qps": 8.0, "answered_qps": 7.5, "latency_p95_ms": 120},
             {"offered_qps": 12.0, "answered_qps": 12.0, "latency_p95_ms": 150},
             {"offered_qps": 12.0, "answered_qps": 10.0, "latency_p95_ms": 160}]
    assert sweep.knee(lines)["knee_qps"] == 8.0


def test_seeds_closer_than_the_rates_are_refused():
    with pytest.raises(SystemExit, match="apart"):
        sweep.main(["--workload", "tiny.open", "--rates", "4,8,12", "--seconds", "2",
                    "--seeds", "10,12"])
