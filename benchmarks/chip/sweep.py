"""Knee sweep of an open-loop cell, made on the chip.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 3,5,7 --seconds 30 --seeds <n>,<m>

In one process, one run of the cell per offered rate and seed: its traffic
file with the rate replaced, seed ``s + i`` for the i-th rate of each seed
``s``; seeds closer together than the number of rates are refused, so that no
two runs share a seed.  Prints one JSON line per run: the rate offered, the
queries answered inside the window per second, the p95 latency, dollars per
1000 answered queries, queries per batch prompt, requests attempted and
failed, ``correct``, the peak device memory and ``logit_gap_mean``.  The last
line gives the knee: the highest offered rate that, with every lower rate,
the answered rate keeps up with (within a tenth) while the p95 latency stays
within twice the lowest rate's, each read at its worst over the seeds (the
lowest answered rate, the highest p95); the cell offers four fifths of it,
written into its traffic file.
"""
import argparse
import copy
import json
import re
import sys

import run


def knee(lines: list) -> dict:
    """The knee of the sweep's lines, as the module's docstring defines it."""
    rates = sorted({ln["offered_qps"] for ln in lines})
    p95 = lambda ln: float("inf") if ln["latency_p95_ms"] is None else ln["latency_p95_ms"]
    worst = {r: (min(ln["answered_qps"] for ln in lines if ln["offered_qps"] == r),
                 max(p95(ln) for ln in lines if ln["offered_qps"] == r))
             for r in rates}
    p95_floor = worst[rates[0]][1]
    k = None
    for r in rates:
        if worst[r][0] < 0.9 * r or worst[r][1] > 2 * p95_floor:
            break
        k = r
    return {"knee_qps": k, "rate_qps": None if k is None else 0.8 * k,
            "lowest_rate_p95_ms": p95_floor}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated queries/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell, cfg, mix = run.resolve_cell(args.workload)
    if mix["load"]["loop"] != "open":
        raise SystemExit("a sweep offers rates: open-loop cells only")
    sys.path.insert(0, run.os.path.join(run.ROOT, "src"))
    rates = [float(r) for r in args.rates.split(",")]
    seeds = [int(s) for s in args.seeds.split(",")]
    if len({s + i for s in seeds for i in range(len(rates))}) < len(seeds) * len(rates):
        raise SystemExit(f"seeds must lie at least {len(rates)} apart: one run per seed")
    run.set_compile_cache()
    lines = []
    for seed in seeds:
        for i, rate in enumerate(rates):
            m = copy.deepcopy(mix)
            m["load"]["rate_qps"] = rate
            result, out = run.run(cell, cfg, m, seed + i, args.seconds, trace=False)
            groups = next(re.search(r"groups (\d+) admitted (\d+)", ln).groups()
                          for ln in out if ln.startswith("windows "))
            met = {k: v["value"] for k, v in result["metrics"].items()}
            line = {"offered_qps": rate, "seed": seed + i,
                    "answered_qps": met["queries_per_s"],
                    "latency_p95_ms": met.get("latency_p95_ms"),
                    "usd_per_kquery": met["usd_per_kquery"],
                    "queries_per_prompt": int(groups[1]) / max(1, int(groups[0])),
                    "attempted": result["attempted"], "failed": result["failed"],
                    "correct": result["correct"],
                    "memory_peak_bytes": result["device"]["memory_peak_bytes"],
                    "logit_gap_mean": result["checks"]["logit_gap_mean"]["value"]}
            lines.append(line)
            print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, **knee(lines)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
