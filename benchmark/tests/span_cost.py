"""What one span costs with the profiler off, for one or more checkouts.

    python3 benchmark/tests/span_cost.py <checkout root> [<checkout root> ...]

A host timing, by hand like the other tools here (through the chip tool
it is the chip's HOST that is timed; nothing runs on the device). Each
root's ``pilosa_tpu/utils/tracing.py`` is loaded by path as a module of
its own, jax imported first so that the span pays the flag test of a
server's process. Two loops, each the best of ``REPEATS`` passes of
about ``N`` spans on one thread: a root span with one tag; and trees
shaped like a request's, one root with ``CHILDREN`` children of one tag
each opened and closed under it (the serving path's case: ids inherited,
and where the tracer reads its CPU clock on some trees only, that share
of them). Then ``N_CONTENDED`` spans of such trees while 15 other threads
spin (the interpreter lock contended), once: the thread's CPU time a span
and its wall time a span, which is a whole switch interval (5 ms)
wherever a span gives the lock away, so that loop is short and ends after
``CONTENDED_S`` at the latest (100,000 such spans of PR 36's parent did
not end in 40 minutes on the chip's host). One JSON line a root,
microseconds a span.
"""

import importlib.util
import json
import os
import sys
import threading
import time

N = 100_000
REPEATS = 7
N_CONTENDED = 2_000
CHILDREN = 10
CONTENDED_S = 15.0  # the contended loop ends here at the latest


def load(root: str):
    path = os.path.join(root, "pilosa_tpu", "utils", "tracing.py")
    spec = importlib.util.spec_from_file_location(f"tracing_{abs(hash(root))}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def best_us(fn, spans: int) -> float:
    best = float("inf")
    for _ in range(REPEATS):
        t = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t)
    return best / spans * 1e6


def measure(root: str) -> dict:
    tracer = load(root).GLOBAL_TRACER
    per_tree = CHILDREN + 1
    n_trees = N // per_tree

    def roots():
        for _ in range(N):
            with tracer.span("bench.root", k=1):
                pass

    def trees(n=n_trees, until=float("inf")):
        """``n`` whole trees, or fewer at ``until``; the spans opened."""
        for k in range(n):
            with tracer.span("bench.parent"):
                for _ in range(CHILDREN):
                    with tracer.span("bench.child", k=1):
                        pass
            if time.perf_counter() > until:
                return (k + 1) * per_tree
        return n * per_tree

    out = {"root": root, "root_span_us": best_us(roots, N),
           "tree_span_us": best_us(trees, n_trees * per_tree)}
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(200))

    others = [threading.Thread(target=spin, daemon=True) for _ in range(15)]
    for t in others:
        t.start()
    try:
        # wall time here is mostly the other threads' turns; the CPU time of
        # this thread is what the spans themselves cost under contention
        w0, t0 = time.perf_counter(), time.thread_time()
        done = trees(N_CONTENDED // per_tree, until=w0 + CONTENDED_S)
        out["contended_spans"] = done
        out["span_cpu_us_contended"] = (time.thread_time() - t0) / done * 1e6
        out["span_wall_us_contended"] = (time.perf_counter() - w0) / done * 1e6
    finally:
        stop.set()
        for t in others:
            t.join()
    return out


def main() -> int:
    import jax  # noqa: F401  (the span's annotation class is looked up, never imported)

    for root in sys.argv[1:] or ["."]:
        print(json.dumps(measure(os.path.abspath(root))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
