"""Test harness configuration.

Forces an 8-device virtual CPU platform (multi-chip sharding tests run on a
``jax.sharding.Mesh`` over these, mirroring how the driver validates the
multi-chip path) and a small shard width so fragment arrays stay tiny.
Must set env vars BEFORE jax / pilosa_tpu are imported anywhere.
"""

import os

os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH_EXP", "16")
# Tests run on CPU with 8 virtual devices (multi-device sharding tests need
# the virtual mesh). The driver sets JAX_PLATFORMS=cpu; the config pin
# below keeps a bare `pytest` on a machine with a chip off the chip too.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "width20: production shard-width e2e suite; launch as "
        "PILOSA_TPU_SHARD_WIDTH_EXP=20 pytest -m width20 tests/test_width20.py",
    )
    config.addinivalue_line(
        "markers",
        "routing: cost-based host/device query-routing suite "
        "(tests/test_routing.py; runs in tier-1 — the marker exists so "
        "`pytest -m routing` scopes to it)",
    )
    config.addinivalue_line(
        "markers",
        "batching: cross-query wave-coalescing suite "
        "(tests/test_scheduler.py; runs in tier-1 — the marker exists so "
        "`pytest -m batching` scopes to it)",
    )
    config.addinivalue_line(
        "markers",
        "faults: fault-tolerance chaos suite — seeded fault injection, "
        "retry/failover/breaker/deadline behavior (tests/test_faults.py; "
        "runs in tier-1 — the marker exists so `pytest -m faults` scopes "
        "to it)",
    )
    config.addinivalue_line(
        "markers",
        "serving: event-driven front-end suite — keep-alive multiplexing, "
        "admission control/backpressure, slow/abusive-client defenses, "
        "connection pooling (tests/test_serving.py; runs in tier-1 — the "
        "marker exists so `pytest -m serving` scopes to it)",
    )
    config.addinivalue_line(
        "markers",
        "spmd: mesh-vs-host equivalence over every PQL read call type on "
        "the 8-virtual-device mesh (tests/test_mesh_spmd.py; runs in "
        "tier-1 — the marker exists so `pytest -m spmd` scopes to it)",
    )
    config.addinivalue_line(
        "markers",
        "residency: tiered compressed device residency suite — container "
        "equivalence across dense/sparse/run, hot/cold promotion and "
        "demotion, byte-ledger concurrency (tests/test_residency.py; runs "
        "in tier-1 — the marker exists so `pytest -m residency` scopes to "
        "it)",
    )
    config.addinivalue_line(
        "markers",
        "multiproc: shard-owning multi-process serving suite — supervisor "
        "lifecycle, SO_REUSEPORT/fd-pass listeners, fleet observability "
        "(tests/test_multiproc.py; the in-process half runs in tier-1, "
        "the subprocess topologies are also marked slow)",
    )
    config.addinivalue_line(
        "markers",
        "observability: flight recorder / EXPLAIN / router-audit suite "
        "(tests/test_flightrec.py; runs in tier-1 — the marker exists so "
        "`pytest -m observability` scopes to it)",
    )
    config.addinivalue_line(
        "markers",
        "workload: workload-intelligence suite — fingerprinting, "
        "heavy-hitter sketch, SLO burn rates, capture→replay "
        "(tests/test_workload.py; runs in tier-1 — the marker exists so "
        "`pytest -m workload` scopes to it)",
    )
    config.addinivalue_line(
        "markers",
        "profiler: continuous profiling & saturation plane suite — "
        "sampling profiler attribution, segment ring, saturation "
        "probes/verdict, lock-contention shim, resource ledger, doctor "
        "(tests/test_profiler.py; runs in tier-1 — the marker exists so "
        "`pytest -m profiler` scopes to it)",
    )
    config.addinivalue_line(
        "markers",
        "ingest: wire-speed bulk-ingest suite — vectorized container "
        "builders, roaring WAL-adopt, batched key translation, loader "
        "backoff, bulk-lane crash recovery (tests/test_ingest.py; runs "
        "in tier-1 — the marker exists so `pytest -m ingest` scopes to "
        "it)",
    )
    config.addinivalue_line(
        "markers",
        "cache: mutation-stamped result-cache suite — key identity, "
        "mutation-race bit-equivalence, invalidation reach, byte-budget "
        "eviction, the event-loop hit fast path, coordinator hits "
        "(tests/test_resultcache.py; runs in tier-1 — the marker exists "
        "so `pytest -m cache` scopes to it)",
    )
    config.addinivalue_line(
        "markers",
        "slow: long/large-scale scenarios excluded from the tier-1 run "
        "(`-m 'not slow'`), e.g. the 10k-concurrent-connection smoke test",
    )


def pytest_sessionfinish(session, exitstatus):
    """Concurrency-sanitizer gate: when the suite ran under
    PILOSA_TPU_SANITIZE=1, fail the session if the instrumented locks
    observed a lock-order cycle, a blocking acquire of a non-loop_safe
    lock on the event-loop thread, or (when PILOSA_TPU_SANITIZE_STATIC
    points at --emit-lock-graph output) a holds-while-acquiring edge the
    static call-graph closure failed to predict.  No-op otherwise."""
    from pilosa_tpu.utils import sanitize

    if not sanitize.enabled():
        return
    problems = sanitize.findings()
    if not problems:
        return
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    for line in problems:
        msg = f"[pilosa-tpu sanitize] {line}"
        if tr is not None:
            tr.write_line(msg, red=True)
        else:
            print(msg)
    session.exitstatus = 3


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def tmp_holder_path(tmp_path):
    return str(tmp_path / "holder")


@pytest.fixture(scope="session")
def certpair(tmp_path_factory):
    """(cert, key) paths of a self-signed localhost certificate."""
    import subprocess

    d = tmp_path_factory.mktemp("tls")
    cert, key = d / "node.crt", d / "node.key"
    subprocess.run(
        [
            "openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
            "-keyout", str(key), "-out", str(cert), "-days", "2",
            "-subj", "/CN=127.0.0.1",
            "-addext", "subjectAltName=IP:127.0.0.1,DNS:localhost",
        ],
        check=True,
        capture_output=True,
    )
    return str(cert), str(key)
