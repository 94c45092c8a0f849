"""CPU rehearsal of a whole run at a tiny size (on-chip guide, section 2).

    JAX_PLATFORMS=cpu PILOSA_TPU_SHARD_WIDTH_EXP=16 \\
        python benchmark/tests/rehearse.py --workload taxi-128.four_queries --shards 2

The same boot, load, warm-up, window, comparison and metric readers as a
run, with the look for a chip replaced (tests/planted.py). What it prints
is no result line: it is marked ``rehearsal`` and its times are the CPU
backend's, never to be written under a metric's name.
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
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--shards", type=int, default=2)
    args = ap.parse_args()
    with planted.on_cpu(args.shards):
        line = bench_run.run(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps({"rehearsal": True, "line": line}))
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
