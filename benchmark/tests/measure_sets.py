"""Two sets of runs of one cell, as the driver makes them, and the spread
of each end-to-end metric by the contract's rule.

    python benchmark/tests/measure_sets.py --workload W --seeds 1,2,3,4,5,6 \\
        [--sets 2] [--trace-seeds 7,8] --out chiprun_out/sets

Each run is the committed command in a process of its own. The spread of
a metric in a set is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; the
bound follows from the wider of the two sets' spreads.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_run(command, workload, seed, seconds, trace, log_path):
    t = time.monotonic()
    proc = subprocess.run(
        command + ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True,
    )
    with open(log_path, "a") as f:
        f.write(f"### seed {seed} trace {trace} rc {proc.returncode} wall {time.monotonic() - t:.1f}s\n")
        f.write(proc.stdout + "\n--- stderr\n" + proc.stderr[-4000:] + "\n")
    if proc.returncode != 0:
        return None
    return json.loads(proc.stdout.strip().splitlines()[-1])


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    os.makedirs(args.out, exist_ok=True)
    log_path = os.path.join(args.out, f"{args.workload}.log")
    seeds = [int(s) for s in args.seeds.split(",")]
    ok, sets = True, []
    for k in range(args.sets):
        lines = []
        for seed in seeds:
            line = one_run(bench["command"], args.workload, seed, bench["run_seconds"], 0, log_path)
            print(json.dumps({"set": k, "seed": seed, "line": line}), flush=True)
            ok = ok and line is not None and line["correct"]
            if line is not None:
                lines.append(line)
        sets.append(lines)
    for seed in (int(s) for s in args.trace_seeds.split(",") if s):
        line = one_run(bench["command"], args.workload, seed, bench["run_seconds"], 1, log_path)
        print(json.dumps({"set": "trace", "seed": seed, "line": line}), flush=True)
        ok = ok and line is not None and line["correct"]
    summary = {}
    for m in bench["end_to_end"]:
        per_set = []
        for lines in sets:
            values = [ln["metrics"][m["name"]]["value"] for ln in lines if m["name"] in ln["metrics"]]
            if len(values) >= 3:
                per_set.append({"median": statistics.median(values), "spread": spread(values),
                                "values": values})
        if per_set:
            summary[m["name"]] = {"sets": per_set, "widest_spread": max(s["spread"] for s in per_set)}
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
