.PHONY: check fix test analyze sanitize chip-smoke

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
