"""Serving-path mesh execution: POST /index/i/query runs SPMD.

VERDICT r1 item 3: a PQL query on a multi-device host must execute as one
sharded program — the stacked field arrays carry NamedSharding over the
(shards × words) mesh and reductions become XLA collectives, not
single-device sums. These tests drive the REAL server stack (HTTP socket
→ handler → API → executor → compiled program) on the 8-virtual-device
CPU platform from conftest.
"""

import json
import urllib.request

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding

from pilosa_tpu.server import Server
from pilosa_tpu.utils.config import Config


@pytest.fixture
def srv(tmp_path):
    s = Server(
        Config(
            bind="127.0.0.1:0",
            data_dir=str(tmp_path / "data"),
            anti_entropy_interval=0,
        )
    )
    s.open()
    # the mesh executor attaches off-thread (boot must not block on
    # accelerator init); these tests assert on sharded execution
    assert s.wait_mesh(60)
    yield s
    s.close()


def call(srv, method, path, body=None):
    url = f"http://127.0.0.1:{srv.port}{path}"
    data = body if isinstance(body, (bytes, type(None))) else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read() or b"{}")


def _device_set(arr) -> set:
    return {d.id for d in arr.sharding.device_set}


def test_server_uses_mesh_on_multidevice_host(srv):
    assert len(jax.devices()) == 8  # conftest's virtual platform
    assert srv.api.mesh_ctx is not None
    assert srv.api.mesh_ctx.n_devices == 8


def test_query_stacks_carry_namedsharding(srv):
    call(srv, "POST", "/index/mi", {})
    call(srv, "POST", "/index/mi/field/f", {})
    # 16 shards of data so the stack's S axis spans every device
    rng = np.random.default_rng(5)
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    cols = rng.choice(16 * SHARD_WIDTH, size=4000, replace=False)
    rows = rng.integers(0, 3, size=4000)
    call(
        srv,
        "POST",
        "/index/mi/field/f/import",
        {"rowIDs": rows.tolist(), "columnIDs": cols.tolist()},
    )

    # pin the mesh route: the cost router would (correctly) serve a
    # query this small from the host engine, which never touches the
    # device stack cache this test exists to inspect
    srv.api.executor.router.mode = "mesh"
    r = call(srv, "POST", "/index/mi/query", b"Count(Intersect(Row(f=0), Row(f=1)))")
    a = set(cols[rows == 0].tolist())
    b = set(cols[rows == 1].tolist())
    assert r["results"] == [len(a & b)]

    # the device-resident stacks must be sharded across the whole mesh
    stacks = srv.api.executor.compiler.stacks._cache
    assert stacks, "query did not populate the stack cache"
    placed = [entry[1] for entry in stacks.values()]
    for arr in placed:
        assert isinstance(arr.sharding, NamedSharding)
        assert len(_device_set(arr)) == 8
        # replicated-everywhere also spans 8 devices — require a real split
        assert not arr.sharding.is_fully_replicated


def test_topn_sum_on_mesh(srv):
    call(srv, "POST", "/index/ms", {})
    call(srv, "POST", "/index/ms/field/cat", {})
    call(
        srv,
        "POST",
        "/index/ms/field/amount",
        {"options": {"type": "int", "min": -1000, "max": 1000}},
    )
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(6)
    n = 3000
    cols = rng.choice(8 * SHARD_WIDTH, size=n, replace=False)
    rows = rng.integers(0, 5, size=n)
    vals = rng.integers(-500, 500, size=n)
    call(
        srv,
        "POST",
        "/index/ms/field/cat/import",
        {"rowIDs": rows.tolist(), "columnIDs": cols.tolist()},
    )
    call(
        srv,
        "POST",
        "/index/ms/field/amount/import-value",
        {"columnIDs": cols.tolist(), "values": vals.tolist()},
    )

    r = call(srv, "POST", "/index/ms/query", b"TopN(cat, n=3)")
    counts = {rid: int((rows == rid).sum()) for rid in range(5)}
    expect = sorted(counts.items(), key=lambda rc: (-rc[1], rc[0]))[:3]
    got = [(e["id"], e["count"]) for e in r["results"][0]]
    assert got == expect

    r = call(srv, "POST", "/index/ms/query", b"Sum(field=amount)")
    assert r["results"][0] == {"value": int(vals.sum()), "count": n}

    r = call(
        srv, "POST", "/index/ms/query", b"Count(Row(amount > 100))"
    )
    assert r["results"] == [int((vals > 100).sum())]


def test_mesh_disabled_by_config(tmp_path):
    s = Server(
        Config(
            bind="127.0.0.1:0",
            data_dir=str(tmp_path / "data2"),
            anti_entropy_interval=0,
            mesh_enabled=False,
        )
    )
    s.open()
    try:
        assert s.api.mesh_ctx is None
    finally:
        s.close()


def test_failed_attach_is_an_error(tmp_path, monkeypatch):
    """A backend that fails to initialize is an error, not a mode: the
    attach thread records what jax raised, open() and wait_mesh()
    re-raise it, and the server re-pins neither its platform nor its
    router."""
    import jax

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "local_devices", no_backend)
    s = Server(
        Config(
            bind="127.0.0.1:0",
            data_dir=str(tmp_path / "d"),
            anti_entropy_interval=0,
        )
    )
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize backend"):
            s.open()
        with pytest.raises(RuntimeError, match="Unable to initialize backend"):
            s.wait_mesh(5)
        assert s.api.executor.router.mode == "auto"
        assert jax.config.jax_platforms == "cpu"
    finally:
        s.close()


def test_server_command_exits_nonzero_without_backend(tmp_path):
    """``python -m pilosa_tpu server`` ends with the backend's own error
    when JAX cannot give it a device — it does not go on serving from
    the host engine (docs/administration.md)."""
    import os
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-m", "pilosa_tpu", "server",
         "--bind", "127.0.0.1:0", "--data-dir", str(tmp_path / "d")],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ, JAX_PLATFORMS="nosuchplatform"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "nosuchplatform" in proc.stderr
    assert "listening" not in proc.stdout
