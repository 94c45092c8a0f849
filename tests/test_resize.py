"""Elastic-resize tests (docs/resize.md): the movement admission lane,
labeled rebalance timeouts, node-remove/pull conflict surfacing,
fragment-checksum convergence, backup/restore through the bulk lane,
and the movement kill-9 chaos extension.

Mirrors tests/test_cluster.py's in-process-cluster harness and
tests/test_durability.py's subprocess crash-recovery pattern."""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from pilosa_tpu import cli
from pilosa_tpu.parallel.movement import (
    MovementLane,
    MovementMeter,
    fragment_checksum,
)
from pilosa_tpu.roaring import serialize
from pilosa_tpu.server import Server
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.config import Config

REPO = Path(__file__).resolve().parent.parent
MOVEMENT_CHILD = REPO / "tests" / "_movement_child.py"


def free_ports(n):
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def make_cluster(tmp_path, n=2, replica_n=1):
    ports = free_ports(n)
    seeds = [f"http://127.0.0.1:{p}" for p in ports]
    servers = []
    for i in range(n):
        cfg = Config(
            bind=f"127.0.0.1:{ports[i]}",
            data_dir=str(tmp_path / f"node{i}"),
            seeds=seeds,
            replica_n=replica_n,
            anti_entropy_interval=0,
            coordinator=(i == 0),
        )
        s = Server(cfg)
        s.open()
        servers.append(s)
    for s in servers:
        s.cluster._heartbeat_once()
    return servers, ports, seeds


def call(port, method, path, body=None, raw=False):
    data = body if isinstance(body, (bytes, type(None))) else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method
    )
    with urllib.request.urlopen(req) as resp:
        payload = resp.read()
        return payload if raw else json.loads(payload or b"{}")


def shutdown(servers):
    for s in servers:
        if s is not None:
            s.close()


def grow(tmp_path, servers, ports, seeds):
    (new_port,) = free_ports(1)
    cfg = Config(
        bind=f"127.0.0.1:{new_port}",
        data_dir=str(tmp_path / f"node{len(servers)}"),
        seeds=seeds + [f"http://127.0.0.1:{new_port}"],
        replica_n=servers[0].config.replica_n,
        anti_entropy_interval=0,
    )
    s = Server(cfg)
    s.open()
    return s, new_port


# ----------------------------------------------------- movement lane (unit)
def test_movement_meter_totals_and_window():
    m = MovementMeter()
    m.record("pull", 1000)
    m.record("pull", 500)
    m.record("push", 200)
    m.note_throttle_wait()
    snap = m.snapshot()
    assert snap["bytesByDirection"] == {"pull": 1500, "push": 200}
    assert snap["bytesTotal"] == 1700
    assert snap["fragmentsTotal"] == 3
    assert snap["throttleWaits"] == 1
    assert snap["recentBytesPerS"] >= 0


def test_movement_lane_token_bucket_paces_bytes():
    # 8 Mbit/s = 1e6 B/s with a 1 s burst: the first MB is free, the
    # next 100 KB must sleep ~0.1 s
    lane = MovementLane(max_concurrent=2, max_mbit=8.0)
    assert lane.throttle(1_000_000) == 0.0
    t0 = time.monotonic()
    slept = lane.throttle(100_000)
    elapsed = time.monotonic() - t0
    assert slept > 0.0 and elapsed >= 0.05
    assert lane.meter.snapshot()["throttleWaits"] == 1
    # unthrottled lane never sleeps
    assert MovementLane(max_mbit=0.0).throttle(10**9) == 0.0


def test_movement_lane_slot_contention_counts_wait():
    lane = MovementLane(max_concurrent=1)
    entered = threading.Event()
    release = threading.Event()
    done = threading.Event()

    def holder_thread():
        with lane.transfer("pull", "i", "f", "standard", 0, peer="p"):
            entered.set()
            release.wait(10)

    def waiter_thread():
        with lane.transfer("pull", "i", "f", "standard", 1, peer="p"):
            pass
        done.set()

    t1 = threading.Thread(target=holder_thread, daemon=True)
    t1.start()
    assert entered.wait(5)
    snap = lane.snapshot()
    assert len(snap["active"]) == 1
    assert snap["active"][0]["state"] == "active"
    t2 = threading.Thread(target=waiter_thread, daemon=True)
    t2.start()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if lane.meter.snapshot()["throttleWaits"] >= 1:
            break
        time.sleep(0.01)
    assert lane.meter.snapshot()["throttleWaits"] >= 1
    release.set()
    assert done.wait(10)
    t1.join(5), t2.join(5)
    snap = lane.snapshot()
    assert snap["active"] == []
    states = [r["state"] for r in snap["recent"]]
    assert states.count("done") == 2


def test_movement_lane_failed_transfer_recorded():
    lane = MovementLane()
    with pytest.raises(RuntimeError):
        with lane.transfer("pull", "i"):
            raise RuntimeError("peer died")
    snap = lane.snapshot()
    assert snap["active"] == []
    assert snap["recent"][-1]["state"] == "failed"


def test_fragment_checksum_is_content_canonical(tmp_path):
    """Different op histories with the same logical bits serialize to
    the same bytes (serialize run-compacts) — equal checksums."""
    from pilosa_tpu.core import Holder
    from pilosa_tpu.roaring import build as rb

    h = Holder(str(tmp_path / "h"))
    h.open()
    try:
        idx = h.create_index("i")
        fld = idx.create_field("f")
        rows = np.zeros(64, dtype=np.uint64)
        cols = np.arange(64, dtype=np.uint64)
        # one fragment built per-bit in two batches...
        fld.import_bulk(rows[:32], cols[:32])
        fld.import_bulk(rows[32:], cols[32:])
        frag_a = fld.view("standard").fragment(0)
        # ...the other adopted as one whole frame
        g = idx.create_field("g")
        view = g.create_view_if_not_exists("standard")
        frag_b = view.create_fragment_if_not_exists(0)
        frag_b.import_roaring(rb.shard_payloads(rows, cols)[0][1])
        sum_a = fragment_checksum(serialize(frag_a.bitmap))
        sum_b = fragment_checksum(serialize(frag_b.bitmap))
        assert sum_a == sum_b
        # and any changed bit changes the digest
        frag_b.set_bit(0, 999)
        assert fragment_checksum(serialize(frag_b.bitmap)) != sum_b
    finally:
        h.close()


# -------------------------------------------- rebalance conflicts (cluster)
def test_wait_rebalanced_timeout_is_labeled(tmp_path, monkeypatch):
    """Satellite 1: a rebalance still running when the timeout expires
    raises a labeled TimeoutError instead of returning silently."""
    from pilosa_tpu.parallel.cluster import (
        Cluster,
        RebalanceInFlightError,
    )

    servers, ports, seeds = make_cluster(tmp_path, n=2)
    third = [None]
    gate = threading.Event()
    try:
        call(ports[0], "POST", "/index/i", {})
        call(ports[0], "POST", "/index/i/field/f", {})
        cols = [s * SHARD_WIDTH + 1 for s in range(12)]
        call(ports[0], "POST", "/index/i/field/f/import",
             {"rowIDs": [1] * len(cols), "columnIDs": cols})

        orig = Cluster._pull_owned_fragments

        def gated(self, sources):
            gate.wait(30)
            return orig(self, sources)

        monkeypatch.setattr(Cluster, "_pull_owned_fragments", gated)
        t = threading.Thread(
            target=lambda: third.__setitem__(
                0, grow(tmp_path, servers, ports, seeds)
            ),
            daemon=True,
        )
        t.start()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if all(len(s.cluster.topology.nodes) == 3 for s in servers):
                break
            time.sleep(0.05)
        assert all(len(s.cluster.topology.nodes) == 3 for s in servers)
        # the old nodes' pull threads are gated: a bounded wait must
        # say so, not time out silently
        with pytest.raises(TimeoutError, match="rebalance pull"):
            servers[0].cluster.wait_rebalanced(timeout=0.2)

        # satellite 1b: node-remove surfaces the in-flight-pull conflict
        victim = servers[1].cluster.me.id
        with pytest.raises(RebalanceInFlightError, match="in flight"):
            servers[0].cluster.remove_node(victim)
        # ...and over HTTP the conflict is a 409, not a 500
        with pytest.raises(urllib.error.HTTPError) as err:
            call(ports[0], "POST", "/internal/cluster/resize/remove-node",
                 {"id": victim})
        assert err.value.code == 409
        body = json.loads(err.value.read())
        assert "rebalance pull in flight" in body["error"]

        gate.set()
        t.join(60)
        assert third[0] is not None
        servers.append(third[0][0])
        for s in servers[:2]:
            s.cluster.wait_rebalanced(30)  # drains fine once ungated
    finally:
        gate.set()
        shutdown(servers)


# ----------------------------------------- checksum convergence (cluster)
def test_internal_status_checksums_converge_across_replicas(tmp_path):
    """Tentpole (b): /internal/status exposes per-fragment content
    checksums; replicas of the same shard agree after anti-entropy."""
    servers, ports, _ = make_cluster(tmp_path, n=2, replica_n=2)
    try:
        call(ports[0], "POST", "/index/i", {})
        call(ports[0], "POST", "/index/i/field/f", {})
        cols = [s * SHARD_WIDTH + 3 for s in range(6)]
        call(ports[0], "POST", "/index/i/field/f/import",
             {"rowIDs": [2] * len(cols), "columnIDs": cols})
        for s in servers:
            s.cluster.sync_holder()
        status = [call(p, "GET", "/internal/status") for p in ports]
        for st in status:
            assert st["state"] == "NORMAL"
            assert "movement" in st
        a, b = (st["checksums"].get("i", {}) for st in status)
        # replica_n=2 on 2 nodes: both hold every fragment, identically
        assert a and a == b
    finally:
        shutdown(servers)


def test_reads_exact_and_unfailing_across_grow_and_shrink(tmp_path):
    """2→3→2 under fire: a client reading from the coordinator the
    whole time sees no failed and no wrong answer through the join's
    hydration pulls and the re-pulls after the remove, and the two
    survivors' fragment checksums agree afterwards."""
    servers, ports, seeds = make_cluster(tmp_path, n=2, replica_n=2)
    try:
        call(ports[0], "POST", "/index/i", {})
        call(ports[0], "POST", "/index/i/field/f", {})
        n_shards = 12
        cols = [s * SHARD_WIDTH + 7 for s in range(n_shards)]
        call(ports[0], "POST", "/index/i/field/f/import",
             {"rowIDs": [1] * n_shards, "columnIDs": cols})
        want = {"results": [n_shards, [{"id": 1, "count": n_shards}]]}
        stop = threading.Event()
        answers, failures = [], []

        def reader():
            while not stop.is_set():
                try:
                    answers.append(call(ports[0], "POST", "/index/i/query",
                                        b"Count(Row(f=1)) TopN(f, n=1)"))
                except Exception as e:  # noqa: BLE001 — the gate counts them
                    failures.append(repr(e))

        t = threading.Thread(target=reader, daemon=True)
        t.start()
        new_srv, _ = grow(tmp_path, servers, ports, seeds)
        servers.append(new_srv)
        for s in servers[:2]:
            s.cluster.wait_rebalanced(30)
        at_three = len(answers)
        for attempt in range(40):
            try:
                call(ports[0], "POST", "/internal/cluster/resize/remove-node",
                     {"id": new_srv.cluster.me.id})
                break
            except urllib.error.HTTPError as e:
                if e.code != 409 or attempt == 39:
                    raise  # only a pull still in flight is expected
                time.sleep(0.25)
        for s in servers[:2]:
            s.cluster.wait_rebalanced(30)
        after = len(answers)
        while len(answers) < after + 5 and not failures:
            time.sleep(0.01)  # a few reads at two nodes again
        stop.set()
        t.join(30)
        assert not t.is_alive()
        assert not failures, failures[:3]
        assert at_three > 0 and all(a == want for a in answers)
        for _ in range(2):
            for s in servers[:2]:
                s.cluster.sync_holder()
        a, b = (
            call(p, "GET", "/internal/status")["checksums"].get("i", {})
            for p in ports
        )
        assert a and a == b
    finally:
        shutdown(servers)


def test_checksum_mismatch_repaired_by_anti_entropy(tmp_path):
    """Satellite 3: a replica whose fragment content diverges (checksum
    mismatch) is repaired by the anti-entropy pass, after which the
    checksums agree again."""
    servers, ports, _ = make_cluster(tmp_path, n=2, replica_n=2)
    try:
        call(ports[0], "POST", "/index/i", {})
        call(ports[0], "POST", "/index/i/field/f", {})
        call(ports[0], "POST", "/index/i/query", b"Set(5, f=1) Set(6, f=1)")
        for s in servers:
            s.cluster.sync_holder()

        sums = lambda p: call(p, "GET", "/internal/status")["checksums"]["i"]  # noqa: E731
        assert sums(ports[0]) == sums(ports[1])

        # diverge one replica behind the cluster's back
        frag = servers[1].holder.index("i").field("f").view("standard").fragment(0)
        frag.clear_bit(1, 5)
        assert sums(ports[0]) != sums(ports[1])

        servers[1].cluster.sync_holder()
        assert sums(ports[0]) == sums(ports[1])
        for p in ports:
            assert call(p, "POST", "/index/i/query",
                        b"Count(Row(f=1))")["results"] == [2]
    finally:
        shutdown(servers)


# -------------------------------------------- movement observability (e2e)
def test_grow_records_movement_metrics_and_debug_surfaces(tmp_path):
    """Satellite 2: a join's hydration pulls ride the movement lane —
    counters, the /debug/resources row, and /debug/cluster all agree."""
    servers, ports, seeds = make_cluster(tmp_path, n=2)
    try:
        call(ports[0], "POST", "/index/i", {})
        call(ports[0], "POST", "/index/i/field/f", {})
        n_shards = 16
        cols = [s * SHARD_WIDTH + 7 for s in range(n_shards)]
        call(ports[0], "POST", "/index/i/field/f/import",
             {"rowIDs": [1] * n_shards, "columnIDs": cols})

        new_srv, new_port = grow(tmp_path, servers, ports, seeds)
        servers.append(new_srv)
        ports.append(new_port)
        for s in servers[:2]:
            s.cluster.wait_rebalanced(30)

        mv = new_srv.cluster.movement.snapshot()
        assert mv["meter"]["fragmentsTotal"] > 0
        assert mv["meter"]["bytesByDirection"].get("pull", 0) > 0
        assert mv["active"] == []  # nothing left in flight

        dbg = call(new_port, "GET", "/debug/cluster")
        assert dbg["movement"]["meter"]["fragmentsTotal"] > 0
        assert dbg["rebalance"]["inFlight"] is False

        res = call(new_port, "GET", "/debug/resources")
        movement_row = res["subsystems"]["movement"]
        assert movement_row["limit"] == new_srv.config.movement_max_concurrent
        assert movement_row["fragmentsTotal"] > 0

        metrics = call(new_port, "GET", "/metrics", raw=True).decode()
        assert "pilosa_tpu_rebalance_bytes_total" in metrics
        assert 'direction="pull"' in metrics
        assert "pilosa_tpu_fragments_moved_total" in metrics

        # counts stay exact from every member after the move
        for p in ports:
            assert call(p, "POST", "/index/i/query",
                        b"Count(Row(f=1))")["results"] == [n_shards]
    finally:
        shutdown(servers)


def test_handoff_push_rides_movement_lane(tmp_path):
    """The AE handoff (old owner streaming a relinquished fragment to
    its new owner) is accounted as a push on the sender's lane."""
    servers, ports, seeds = make_cluster(tmp_path, n=2)
    try:
        call(ports[0], "POST", "/index/i", {})
        call(ports[0], "POST", "/index/i/field/f", {})
        n_shards = 16
        cols = [s * SHARD_WIDTH + 9 for s in range(n_shards)]
        call(ports[0], "POST", "/index/i/field/f/import",
             {"rowIDs": [1] * n_shards, "columnIDs": cols})
        new_srv, new_port = grow(tmp_path, servers, ports, seeds)
        servers.append(new_srv)
        ports.append(new_port)
        for s in servers[:2]:
            s.cluster.wait_rebalanced(30)
        for s in servers:
            s.cluster.sync_holder()  # handoff + drop of relinquished shards
        pushed = sum(
            s.cluster.movement.meter.snapshot()["bytesByDirection"].get("push", 0)
            for s in servers
        )
        assert pushed > 0
        for p in ports:
            assert call(p, "POST", "/index/i/query",
                        b"Count(Row(f=1))")["results"] == [n_shards]
    finally:
        shutdown(servers)


def test_warmup_touches_adopted_fragments(tmp_path):
    """Tentpole (c): warm-up drives PROMOTE_TOUCHES local queries per
    adopted row so the residency tier promotes the new node's shards —
    set fields only, non-standard views and keyed fields skipped."""
    from pilosa_tpu.executor import residency

    servers, ports, _ = make_cluster(tmp_path, n=2)
    try:
        call(ports[0], "POST", "/index/i", {})
        call(ports[0], "POST", "/index/i/field/f", {})
        call(ports[0], "POST", "/index/i/field/v",
             {"options": {"type": "int", "min": 0, "max": 100}})
        call(ports[0], "POST", "/index/i/field/f/import",
             {"rowIDs": [1, 2], "columnIDs": [3, 4]})
        srv = next(  # warm-up only touches fragments held LOCALLY
            s for s in servers
            if s.holder.index("i")
            and 0 in s.holder.index("i").available_shards()
        )
        seen = []
        api = srv.api
        orig_query = api.query

        def counting_query(index, pql, shards=None, **kw):
            seen.append((index, pql, tuple(shards or ())))
            return orig_query(index, pql, shards=shards, **kw)

        api.query = counting_query
        try:
            srv.cluster._warmup_adopted([
                ("i", "f", "standard", 0),
                ("i", "f", "ts_2024", 0),   # non-standard view: skipped
                ("i", "v", "standard", 0),  # int field: skipped
                ("i", "gone", "standard", 0),  # unknown field: skipped
            ])
        finally:
            api.query = orig_query
        assert seen, "warm-up issued no queries"
        assert all(idx == "i" and "Row(f=" in pql for idx, pql, _ in seen)
        assert all(sh == (0,) for _, _, sh in seen)
        # each row touched exactly PROMOTE_TOUCHES times
        per_row = {}
        for _, pql, _ in seen:
            per_row[pql] = per_row.get(pql, 0) + 1
        assert set(per_row.values()) == {residency.PROMOTE_TOUCHES}
    finally:
        shutdown(servers)


# --------------------------------------------------- backup/restore (CLI)
def _seed_backup_source(port):
    call(port, "POST", "/index/src", {"options": {"keys": True}})
    call(port, "POST", "/index/src/field/tag", {"options": {"keys": True}})
    call(port, "POST", "/index/src/field/bits", {})
    call(port, "POST", "/index/src/query",
         b'Set("alpha", tag="red") Set("beta", tag="red") Set("gamma", tag="blue")')
    cols = [s * SHARD_WIDTH + 11 for s in range(5)]
    call(port, "POST", "/index/src/field/bits/import",
         {"rowIDs": [4] * len(cols), "columnIDs": cols})


def _assert_restored(port, index):
    r = call(port, "POST", f"/index/{index}/query", b'Count(Row(tag="red"))')
    assert r["results"] == [2]
    r = call(port, "POST", f"/index/{index}/query", b'Count(Row(tag="blue"))')
    assert r["results"] == [1]
    r = call(port, "POST", f"/index/{index}/query", b"Count(Row(bits=4))")
    assert r["results"] == [5]
    # translate bindings restored: the SAME keys resolve, no new allocs
    r = call(port, "POST", f"/index/{index}/query", b'Row(tag="red")')
    assert sorted(r["results"][0].get("keys", [])) == ["alpha", "beta"]


def test_backup_restore_roundtrip_cli(tmp_path, capsys):
    """Satellite/tentpole (a): `backup` tars fragments + translate +
    schema off a live cluster; `restore` replays them into a DIFFERENT
    cluster through the public bulk lane — counts and key bindings
    exact, and the tar's checksums verify each adopted frame."""
    src_servers, src_ports, _ = make_cluster(tmp_path / "src", n=1)
    tar_path = tmp_path / "src.backup.tar"
    try:
        _seed_backup_source(src_ports[0])
        rc = cli.main([
            "backup", "--host", f"127.0.0.1:{src_ports[0]}",
            "-i", "src", "-o", str(tar_path),
        ])
        assert rc == 0
        out = capsys.readouterr().out
        assert "fragments" in out and str(tar_path) in out
    finally:
        shutdown(src_servers)
    assert tar_path.exists()

    # restore into a fresh TWO-node cluster: the coordinator fans each
    # frame out to whatever owns the shard under the new topology
    dst_servers, dst_ports, _ = make_cluster(tmp_path / "dst", n=2)
    try:
        rc = cli.main([
            "restore", str(tar_path),
            "--host", f"127.0.0.1:{dst_ports[0]}",
        ])
        assert rc == 0
        for p in dst_ports:
            _assert_restored(p, "src")
        # checksum convergence: what landed matches the manifest
        import tarfile

        with tarfile.open(tar_path) as tar:
            manifest = json.loads(
                tar.extractfile("src/manifest.json").read()
            )
        want = {
            f"{r['field']}/{r['view']}/{r['shard']}": r["checksum"]
            for r in manifest["fragments"]
        }
        got: dict = {}
        for p in dst_ports:
            got.update(call(p, "GET", "/internal/status")["checksums"]["src"])
        assert got == want
    finally:
        shutdown(dst_servers)


def test_restore_rename_lands_under_new_index(tmp_path, capsys):
    src_servers, src_ports, _ = make_cluster(tmp_path / "src", n=1)
    tar_path = tmp_path / "b.tar"
    try:
        _seed_backup_source(src_ports[0])
        assert cli.main(["backup", "--host", f"127.0.0.1:{src_ports[0]}",
                         "-i", "src", "-o", str(tar_path)]) == 0
        # restore back into the SAME cluster under a new name
        assert cli.main(["restore", str(tar_path),
                         "--host", f"127.0.0.1:{src_ports[0]}",
                         "--rename", "copy"]) == 0
        _assert_restored(src_ports[0], "copy")
        _assert_restored(src_ports[0], "src")  # original untouched
    finally:
        shutdown(src_servers)


def test_backup_missing_index_fails_cleanly(tmp_path, capsys):
    servers, ports, _ = make_cluster(tmp_path, n=1)
    try:
        rc = cli.main(["backup", "--host", f"127.0.0.1:{ports[0]}",
                       "-i", "nope", "-o", str(tmp_path / "x.tar")])
        assert rc == 1
        assert "not found" in capsys.readouterr().err
        assert not (tmp_path / "x.tar").exists()
    finally:
        shutdown(servers)


# ------------------------------------------- kill-9 movement chaos (slow)
MOVEMENT_KILL_POINTS = [
    # mid-fragment-pull: the hydration adopt's union WAL append is cut
    # short on disk, then SIGKILL — the pulled frame is torn but every
    # locally acknowledged batch must survive, and the re-pull converges
    ("mid-fragment-pull", "pull",
     {"op": "wal-append", "action": "torn", "cap_bytes": 17,
      "then": "kill", "path": "fragments/", "after": 0}),
    # mid-restore-adopt: same death inside an EXISTING fragment's WAL —
    # the torn restore frame must not take acknowledged bits with it
    ("mid-restore-adopt", "restore",
     {"op": "wal-append", "action": "torn", "cap_bytes": 17,
      "then": "kill", "path": "fragments/", "after": 0}),
]


@pytest.mark.slow
@pytest.mark.parametrize(
    "point,mode,rule", MOVEMENT_KILL_POINTS,
    ids=[p for p, _, _ in MOVEMENT_KILL_POINTS],
)
def test_kill9_movement_zero_acknowledged_loss(tmp_path, point, mode, rule):
    """Satellite 3 / tentpole (c): SIGKILL mid-movement-adopt loses zero
    acknowledged writes, and re-pulling the same frame converges to the
    fault-free oracle's content checksum."""
    data_dir = str(tmp_path / "holder")
    env = dict(os.environ, PILOSA_TPU_SHARD_WIDTH_EXP="16",
               JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    proc = subprocess.run(
        [sys.executable, str(MOVEMENT_CHILD), data_dir,
         json.dumps([rule]), mode],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode == -9, (
        f"{point}: child must die by SIGKILL at the armed point "
        f"(rc={proc.returncode})\n{proc.stdout}\n{proc.stderr}"
    )
    assert "ADOPTED" not in proc.stdout, (
        f"{point}: the adopt completed before the armed kill"
    )
    acked = [
        int(line.split()[1])
        for line in proc.stdout.splitlines()
        if line.startswith("ACK ")
    ]
    assert acked, f"{point}: no batch was acknowledged before the kill"

    sys.path.insert(0, str(REPO / "tests"))
    try:
        from _movement_child import batch_bits, movement_frame
    finally:
        sys.path.pop(0)
    from pilosa_tpu.core import Holder

    shard, frame = movement_frame(mode)
    h = Holder(data_dir)
    h.open()
    try:
        view = h.index("i").field("f").view("standard")
        frag0 = view.fragment(0)
        assert frag0 is not None
        assert not (frag0.last_recovery or {}).get("quarantined", False)
        lost = []
        for b in acked:
            rows, cols = batch_bits(b)
            for r, c in zip(rows.tolist(), cols.tolist()):
                if not frag0.contains(r, c):
                    lost.append((b, r, c))
        assert not lost, (
            f"{point}: {len(lost)} acknowledged bits lost after SIGKILL "
            f"mid-movement-adopt: {lost[:5]}"
        )
        # the re-pull: adopt the SAME frame again (idempotent union)
        frag = view.create_fragment_if_not_exists(shard)
        frag.import_roaring(frame)
        recovered_sum = fragment_checksum(serialize(frag.bitmap))
    finally:
        h.close()

    # fault-free oracle: the same ingest + adopt with no faults
    oracle_dir = str(tmp_path / "oracle")
    oracle = subprocess.run(
        [sys.executable, str(MOVEMENT_CHILD), oracle_dir, "[]", mode],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert oracle.returncode == 0, oracle.stderr
    assert "ADOPTED" in oracle.stdout
    ho = Holder(oracle_dir)
    ho.open()
    try:
        ofrag = ho.index("i").field("f").view("standard").fragment(shard)
        oracle_sum = fragment_checksum(serialize(ofrag.bitmap))
    finally:
        ho.close()
    assert recovered_sum == oracle_sum, (
        f"{point}: re-pull did not converge to the oracle checksum"
    )
