# bash for pipefail: the bench-observability gate must not be masked
# by the artifact tee
SHELL := /bin/bash

.PHONY: check fix test analyze sanitize chip-smoke bench-ingest bench-residency bench-observability bench-workload bench-profile bench-cache bench-multiproc bench-resize

# the same gate CI runs: repo analyzer, then ruff/mypy when installed
check:
	python tools/check.py

# apply the analyzer's mechanical autofixes (with-locks, monotonic)
fix:
	python tools/check.py --fix

analyze:
	python -m tools.analysis pilosa_tpu

# tier-1 test suite (see ROADMAP.md for the exact CI invocation)
test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow'

# the served path on the chip, end to end (docs: PERF.md, the verify
# skill's entry 10): run it through the chip tool; exits non-zero with no
# accelerator. `python chip_smoke.py --mesh` is the four-chip variant.
chip-smoke:
	python chip_smoke.py

# tier-1 under the runtime concurrency sanitizer (docs/concurrency.md):
# every make_lock site instrumented, the observed holds-while-acquiring
# graph checked against the analyzer's static closure; the conftest gate
# fails the session on any cycle, loop-thread blocking acquire, or
# observed edge the static graph did not predict
sanitize:
	python -m tools.analysis --emit-lock-graph pilosa_tpu > .sanitize-static.json
	JAX_PLATFORMS=cpu PILOSA_TPU_SANITIZE=1 \
		PILOSA_TPU_SANITIZE_STATIC=.sanitize-static.json \
		python -m pytest tests/ -q -m 'not slow'

# mixed ingest+read row, the wire-speed sustained bulk-lane row
# (docs/ingest.md — exits non-zero below 10 M set-bits/s through the
# loader), and the restart-to-serving rows (docs/durability.md); also
# exits non-zero when mixed read p95 breaks the 2x read-only gate
bench-ingest:
	set -o pipefail; PILOSA_BENCH_ALL_CHILD=ingest python bench_all.py | tee BENCH_INGEST_r14.json

# tiered compressed residency row (docs/device-residency.md): an index
# whose uncompressed stack is >=4x the device budget, hot-set QPS vs the
# forced-host baseline + compression ratio; exits non-zero below 1.0x
bench-residency:
	PILOSA_BENCH_ALL_CHILD=residency python bench_all.py

# flight-recorder + router-audit overhead row (docs/observability.md):
# instrumented-on vs instrumented-off c1 p50/p99 on the config8 count
# shape; exits non-zero if the always-on layer costs >3% p50
bench-observability:
	set -o pipefail; PILOSA_BENCH_ALL_CHILD=observability python bench_all.py | tee BENCH_OBS_r10.json

# continuous profiling & saturation plane row (docs/profiling.md):
# plane-on vs plane-off c1 p50 on the config8 count shape (exits
# non-zero past 1.03x, inertness checked both ways) + the c1/c8/c32/c64
# saturation sweep recording worker utilization, loop-lag p99, GIL-wait
# estimate, and the binding-resource verdict per level
bench-profile:
	set -o pipefail; PILOSA_BENCH_ALL_CHILD=profile python bench_all.py | tee BENCH_PROFILE_r12.json

# workload-intelligence plane row (docs/workload.md): capture-on vs
# capture-off c1 p50 on the config8 count shape (exits non-zero past
# 1.03x) + capture→replay of the config8 mix with per-shape QPS
# ordering and fidelity-ratio gates
bench-workload:
	set -o pipefail; PILOSA_BENCH_ALL_CHILD=workload python bench_all.py | tee BENCH_WORKLOAD_r11.json

# mutation-stamped result-cache row (docs/result-cache.md): Zipfian mix
# hit fraction, hot-tail QPS of event-loop hits vs the cache-off
# baseline (exits non-zero below 5x), and cache-on vs cache-off c1 p50
# on never-repeating shapes (exits non-zero past 1.03x)
bench-cache:
	set -o pipefail; PILOSA_BENCH_ALL_CHILD=cache python bench_all.py | tee BENCH_CACHE_r17.json

bench-multiproc:
	set -o pipefail; PILOSA_BENCH_ALL_CHILD=multiproc python bench_all.py | tee BENCH_MULTIPROC_r19.json

# live elastic resize under fire (docs/resize.md): 2→3→2 while the
# recorded config8 mix replays + paced bulk ingest streams frames;
# exits non-zero on any failed/diverged query, broken convergence
# (survivor checksums / acked ingest bits), or acknowledged loss in
# the kill-9 mid-pull chaos leg; p95 and movement-rate gates are
# hardware-aware (waived-and-recorded on a core-starved box)
bench-resize:
	set -o pipefail; PILOSA_BENCH_ALL_CHILD=resize python bench_all.py | tee BENCH_RESIZE_r20.json
