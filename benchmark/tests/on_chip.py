"""Runs of a cell on the chip that are not results: the sound program and
the control (the reference with a guarantee broken, put in the program's
place) on the same replies, several seeds in one call.

    python benchmark/tests/on_chip.py --workload W --seeds 1,2,3 --seconds 20 [--trace]

One line per run: the run's own last line with the control's verdict,
marked ``on_chip``. The parent never touches jax, so the runs follow each
other in one process.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import run as bench_run  # noqa: E402
from benchmark.tests import planted  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args()
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        verdict = {}
        with planted.control(verdict):
            line = bench_run.run(args.workload, seed, args.seconds, args.trace)
        print(json.dumps({"on_chip": True, "seed": seed, "line": line,
                          "control": {k: verdict[k] for k in ("compared", "mismatched")}}), flush=True)
        ok = ok and line["correct"] and verdict["mismatched"] > 0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
