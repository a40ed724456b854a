"""Knee sweep of an open-loop cell, made on the chip.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 3,5,7 --seconds 30 --seed <n>

In one process, one run of the cell per offered rate: its traffic file with
the rate replaced, the next seed for each rate.  Prints one JSON line per
rate: the rate offered, the queries answered inside the window per second,
the p95 latency, queries per batch prompt and ``correct``.  The knee is the
highest offered rate that the answered rate keeps up with (within a tenth)
while the p95 latency stays within twice the lowest rate's; the cell offers
four fifths of it, written into its traffic file.
"""
import argparse
import copy
import json
import re
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated queries/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell, cfg, mix = run.resolve_cell(args.workload)
    if mix["load"]["loop"] != "open":
        raise SystemExit("a sweep offers rates: open-loop cells only")
    sys.path.insert(0, run.os.path.join(run.ROOT, "src"))
    run.set_compile_cache()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = copy.deepcopy(mix)
        m["load"]["rate_qps"] = rate
        result, lines = run.run(cell, cfg, m, args.seed + i, args.seconds, trace=False)
        groups = next(re.search(r"groups (\d+) admitted (\d+)", ln).groups()
                      for ln in lines if ln.startswith("windows "))
        met = {k: v["value"] for k, v in result["metrics"].items()}
        print(json.dumps({"offered_qps": rate, "answered_qps": met["queries_per_s"],
                          "latency_p95_ms": met.get("latency_p95_ms"),
                          "usd_per_kquery": met["usd_per_kquery"],
                          "queries_per_prompt": int(groups[1]) / max(1, int(groups[0])),
                          "attempted": result["attempted"], "failed": result["failed"],
                          "correct": result["correct"],
                          "memory_peak_bytes": result["device"]["memory_peak_bytes"],
                          "logit_gap": result["checks"]["logit_gap"]["value"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
