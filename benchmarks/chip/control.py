"""Readings that set the limit of ``logit_gap_mean``, made on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 --seconds 15

In one process, for each seed: one run of the cell as the benchmark makes it
(a short window at the cell's own load), the gaps of its served bf16 tokens
against the float32 reference, and the gaps of the int8 control on the same
prompts and served tokens: at each position, the token the reference put
through int8 projections ranks first.  The control's gaps go through the
same ``correct`` comparison as the program's and have to come out as not
correct.  Prints one JSON line per seed and a summary: the largest reading of
the program and the smallest of the control, for each reading of the gaps.  The benchmark's own runs never
run the control.
"""
import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell, cfg, mix = run.resolve_cell(args.workload)
    sys.path.insert(0, run.os.path.join(run.ROOT, "src"))
    run.set_compile_cache()

    program_gaps = run.logit_gaps
    control = {}

    def both(arch, served, seed, length, int8=False):
        control["stats"] = run.gap_stats(program_gaps(arch, served, seed, length, int8=True))
        gaps = program_gaps(arch, served, seed, length)
        control["program"] = run.gap_stats(gaps)
        return gaps

    run.logit_gaps = both
    program, ctl = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        control.clear()
        result, _ = run.run(cell, cfg, mix, seed, args.seconds, trace=False)
        numbers = {k: v["value"] for k, v in result["checks"].items()}
        limits = {k: v["limit"] for k, v in result["checks"].items()}
        control_correct = run.judge(dict(numbers, logit_gap_mean=control["stats"]["mean"]), limits)
        program.append(control["program"])
        ctl.append(control["stats"])
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "control_correct": control_correct,
                          "program": control["program"], "control": control["stats"],
                          "metrics": result["metrics"], "checks": result["checks"]}),
              flush=True)
    print(json.dumps({"workload": args.workload, **{
        f"{k}: program largest, control smallest": [max(p[k] for p in program),
                                                    min(c[k] for c in ctl)]
        for k in ("max", "mean", "flip_share")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
