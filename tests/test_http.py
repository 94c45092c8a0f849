"""HTTP API tests — the reference's route surface over a live server.

Mirrors http/handler_test.go: real sockets, JSON bodies, error codes."""

import json
import urllib.error
import urllib.parse
import urllib.request

import pytest

from pilosa_tpu.server import Server
from pilosa_tpu.utils.config import Config


@pytest.fixture
def srv(tmp_path):
    s = Server(Config(bind="127.0.0.1:0", data_dir=str(tmp_path / "data"),
                      anti_entropy_interval=0))
    s.open()
    yield s
    s.close()


def call(srv, method, path, body=None, raw=False):
    url = f"http://127.0.0.1:{srv.port}{path}"
    data = body if isinstance(body, (bytes, type(None))) else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req) as resp:
        payload = resp.read()
        return payload if raw else json.loads(payload or b"{}")


def test_full_http_workflow(srv):
    assert call(srv, "POST", "/index/i", {"options": {}}) == {"success": True}
    assert call(srv, "POST", "/index/i/field/f", {"options": {}})["success"]
    # writes via PQL
    r = call(srv, "POST", "/index/i/query", b"Set(1, f=1) Set(3, f=1) Set(3, f=2)")
    assert r["results"] == [True, True, True]
    r = call(srv, "POST", "/index/i/query", b"Row(f=1)")
    assert r["results"][0]["columns"] == [1, 3]
    r = call(srv, "POST", "/index/i/query", b"Count(Intersect(Row(f=1), Row(f=2)))")
    assert r["results"] == [1]
    # schema
    schema = call(srv, "GET", "/schema")
    assert schema["indexes"][0]["name"] == "i"
    assert schema["indexes"][0]["fields"][0]["name"] == "f"
    idx = call(srv, "GET", "/index/i")
    assert idx["name"] == "i"


def test_invalid_names_rejected(srv):
    for bad in ("UPPER", "1leading", "has space", "x" * 65, "<script>"):
        with pytest.raises(urllib.error.HTTPError) as e:
            call(srv, "POST", f"/index/{urllib.parse.quote(bad)}", {})
        assert e.value.code == 400
    call(srv, "POST", "/index/ok-name_2", {})
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "POST", "/index/ok-name_2/field/Bad", {})
    assert e.value.code == 400


def test_console_served_at_root(srv):
    raw = call(srv, "GET", "/", raw=True)
    html = raw.decode()
    assert html.startswith("<!DOCTYPE html>")
    # the console drives these endpoints; keep the markers stable
    for marker in ("/schema", "/status", "query", "pilosa-tpu"):
        assert marker in html


def test_import_endpoints(srv):
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    call(srv, "POST", "/index/i/field/v", {"options": {"type": "int"}})
    call(
        srv, "POST", "/index/i/field/f/import",
        {"rowIDs": [1, 1, 2], "columnIDs": [10, 20, 10]},
    )
    call(
        srv, "POST", "/index/i/field/v/import-value",
        {"columnIDs": [10, 20], "values": [5, -3]},
    )
    r = call(srv, "POST", "/index/i/query", b"Count(Row(f=1))")
    assert r["results"] == [2]
    r = call(srv, "POST", "/index/i/query", b"Sum(field=v)")
    assert r["results"] == [{"value": 2, "count": 2}]
    # shards param
    r = call(srv, "POST", "/index/i/query?shards=0", b"Count(Row(f=1))")
    assert r["results"] == [2]


def test_import_roaring_endpoint(srv):
    import numpy as np

    from pilosa_tpu import roaring

    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    bm = roaring.Bitmap.from_values(np.array([5, 6, 7], dtype=np.uint64))  # row 0
    call(srv, "POST", "/index/i/field/f/import-roaring/0", roaring.serialize(bm))
    r = call(srv, "POST", "/index/i/query", b"Row(f=0)")
    assert r["results"][0]["columns"] == [5, 6, 7]


def test_export_csv(srv):
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    call(srv, "POST", "/index/i/query", b"Set(1, f=1) Set(2, f=3)")
    csv = call(srv, "GET", "/export?index=i&field=f", raw=True).decode()
    assert csv == "1,1\n3,2\n"


def test_status_info_version_metrics(srv):
    call(srv, "POST", "/index/i", {})
    assert call(srv, "GET", "/status")["state"] == "NORMAL"
    assert call(srv, "GET", "/info")["shardWidth"] > 0
    assert "version" in call(srv, "GET", "/version")
    call(srv, "POST", "/index/i/query", b"Count(Union())")
    metrics = call(srv, "GET", "/metrics", raw=True).decode()
    assert "pilosa_tpu_http_requests" in metrics
    assert "query_seconds" in metrics
    assert "spans" in call(srv, "GET", "/debug/traces")
    assert "counters" in call(srv, "GET", "/debug/vars")


def test_error_codes(srv):
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "POST", "/index/ghost/query", b"Count(Row(f=1))")
    assert e.value.code == 400
    assert "not found" in json.loads(e.value.read())["error"]
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "GET", "/nope")
    assert e.value.code == 404
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "POST", "/index/i/query", b"Row(f=")
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "POST", "/index/i/field/f/import", b"{bad json")
    assert e.value.code == 400


def test_delete_endpoints(srv):
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    assert call(srv, "DELETE", "/index/i/field/f")["success"]
    assert call(srv, "DELETE", "/index/i")["success"]
    with pytest.raises(urllib.error.HTTPError):
        call(srv, "GET", "/index/i")


def test_schema_apply_and_persistence(srv, tmp_path):
    schema = {
        "indexes": [
            {
                "name": "i2",
                "options": {"keys": False},
                "fields": [{"name": "g", "options": {"type": "int"}}],
            }
        ]
    }
    call(srv, "POST", "/schema", schema)
    got = call(srv, "GET", "/schema")
    assert got["indexes"][0]["name"] == "i2"
    assert got["indexes"][0]["fields"][0]["options"]["type"] == "int"


def test_max_writes_per_request_enforced(tmp_path):
    """Oversized import payloads and multi-write queries get 413
    (reference: server/config.go max-writes-per-request)."""
    s = Server(Config(bind="127.0.0.1:0", data_dir=str(tmp_path / "mw"),
                      anti_entropy_interval=0, max_writes_per_request=3))
    s.open()
    try:
        call(s, "POST", "/index/i", {})
        call(s, "POST", "/index/i/field/f", {})
        call(s, "POST", "/index/i/field/v", {"options": {"type": "int"}})
        # at the limit: fine
        call(s, "POST", "/index/i/field/f/import",
             {"rowIDs": [1, 2, 3], "columnIDs": [1, 2, 3]})
        # over the limit: 413
        with pytest.raises(urllib.error.HTTPError) as e:
            call(s, "POST", "/index/i/field/f/import",
                 {"rowIDs": [1, 2, 3, 4], "columnIDs": [1, 2, 3, 4]})
        assert e.value.code == 413
        with pytest.raises(urllib.error.HTTPError) as e:
            call(s, "POST", "/index/i/field/v/import",
                 {"columnIDs": [1, 2, 3, 4], "values": [9, 9, 9, 9]})
        assert e.value.code == 413
        # PQL with too many write calls: 413; reads unaffected
        with pytest.raises(urllib.error.HTTPError) as e:
            call(s, "POST", "/index/i/query",
                 b"Set(1, f=1) Set(2, f=1) Set(3, f=1) Set(4, f=1)")
        assert e.value.code == 413
        r = call(s, "POST", "/index/i/query", b"Set(9, f=1) Count(Row(f=1))")
        assert r["results"][0] is True
        # nothing from the rejected batch landed
        r = call(s, "POST", "/index/i/query", b"Row(f=1)")
        assert 4 not in r["results"][0]["columns"]
    finally:
        s.close()


def test_fragment_export_formats(srv):
    """GET …/fragment/data serves the fragment bitmap in the pilosa
    layout or (format=official) the stock 32-bit RoaringFormatSpec;
    both round-trip through the roaring reader."""
    import numpy as np

    from pilosa_tpu import roaring
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    call(srv, "POST", "/index/fx", {})
    call(srv, "POST", "/index/fx/field/f", {})
    call(srv, "POST", "/index/fx/query", b"Set(1, f=0) Set(9, f=0) Set(5, f=2)")
    import struct

    for fmt, cookies in (("pilosa", {12348}), ("official", {12346, 12347})):
        raw = call(
            srv, "GET", f"/index/fx/field/f/fragment/data?shard=0&format={fmt}",
            raw=True,
        )
        assert struct.unpack_from("<H", raw)[0] in cookies  # wire layout
        b, consumed = roaring.deserialize(raw)
        assert consumed == len(raw)
        want = {1, 9, 2 * SHARD_WIDTH + 5}
        assert set(b.values().tolist()) == want
    # empty shard serves an empty bitmap, still parseable
    raw = call(srv, "GET", "/index/fx/field/f/fragment/data?shard=7", raw=True)
    b, _ = roaring.deserialize(raw)
    assert b.count() == 0


def test_long_query_log_to_file(tmp_path):
    """log-path routes server log lines (long-query warnings) to a file
    (reference: Config.LogPath + the Logger interface)."""
    log_file = tmp_path / "server.log"
    s = Server(
        Config(
            bind="127.0.0.1:0",
            data_dir=str(tmp_path / "data"),
            anti_entropy_interval=0,
            long_query_time=0.000001,  # everything is "long"
            log_path=str(log_file),
        )
    )
    s.open()
    try:
        call(s, "POST", "/index/lq", {})
        call(s, "POST", "/index/lq/field/f", {})
        call(s, "POST", "/index/lq/query", b"Count(Row(f=1))")
    finally:
        s.close()
    text = log_file.read_text()
    assert "long query" in text and "index=lq" in text


def test_query_profile_schema(srv):
    """?profile=true returns a per-call / per-shard timing breakdown;
    the default (profile-off) response shape is unchanged."""
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    call(srv, "POST", "/index/i/query", b"Set(1, f=1) Set(3, f=2)")
    plain = call(srv, "POST", "/index/i/query", b"Count(Row(f=1))")
    assert "profile" not in plain and plain["results"] == [1]
    # pin the device engine: this test asserts the DEVICE profile shape
    # (the _readback wave); a query this small would otherwise be
    # host-routed and pay no readback at all (docs/query-routing.md)
    srv.api.executor.router.mode = "device"
    r = call(srv, "POST", "/index/i/query?profile=true", b"Count(Row(f=1))")
    assert r["results"] == [1]
    p = r["profile"]
    assert set(p) >= {"traceID", "totalSeconds", "calls", "fanout"}
    assert len(p["traceID"]) == 32  # 128-bit hex
    assert p["totalSeconds"] > 0
    counts = [e for e in p["calls"] if e["call"] == "Count"]
    assert counts and counts[0]["seconds"] >= 0
    assert counts[0]["shards"] == [0]
    assert counts[0]["route"] == "device"  # the router's pick, surfaced
    # the deferred-readback wave is accounted separately
    assert any(e["call"] == "_readback" for e in p["calls"])
    # single-node: no fan-out legs
    assert p["fanout"] == []

    # host-routed profile: same shape, route=host, and NO readback wave
    srv.api.executor.router.mode = "host"
    r = call(srv, "POST", "/index/i/query?profile=true", b"Count(Row(f=1))")
    assert r["results"] == [1]
    hcalls = r["profile"]["calls"]
    assert [e for e in hcalls if e["call"] == "Count"][0]["route"] == "host"
    assert not any(e["call"] == "_readback" for e in hcalls)
    srv.api.executor.router.mode = "auto"


def test_trace_spans_have_identity(srv):
    """Every recorded span carries 128-bit trace / 64-bit span ids, and
    /debug/traces?trace_id= filters to one trace."""
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    r = call(srv, "POST", "/index/i/query?profile=true", b"Count(Row(f=1))")
    tid = r["profile"]["traceID"]
    spans = call(srv, "GET", f"/debug/traces?trace_id={tid}")["spans"]
    assert spans and all(s["traceID"] == tid for s in spans)
    names = {s["name"] for s in spans}
    assert "http.query" in names and "pql.query" in names
    assert any(s["name"].startswith("executor.") for s in spans)
    by_id = {s["spanID"]: s for s in spans}
    # executor span parents (transitively) onto the HTTP span
    execs = [s for s in spans if s["name"] == "executor.Count"]
    assert execs and by_id[execs[0]["parentSpanID"]]["name"] == "pql.query"
    assert all(len(s["spanID"]) == 16 for s in spans)
    # chrome export of one trace is well-formed
    ct = call(srv, "GET", f"/debug/traces?format=chrome&trace_id={tid}")
    events = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert events and all(e["args"]["traceID"] == tid for e in events)


def test_metrics_query_seconds_histogram(srv):
    """/metrics exposes query_seconds as a Prometheus histogram:
    cumulative _bucket{le=} series plus _sum/_count."""
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    call(srv, "POST", "/index/i/query", b"Count(Row(f=1))")
    text = call(srv, "GET", "/metrics", raw=True).decode()
    assert "# TYPE pilosa_tpu_query_seconds histogram" in text
    assert 'pilosa_tpu_query_seconds_bucket{index="i",le="+Inf"} 1' in text
    assert "pilosa_tpu_query_seconds_sum" in text
    assert "pilosa_tpu_query_seconds_count" in text
    # the executor's per-call histograms ride the same exposition
    assert "pilosa_tpu_executor_call_seconds_bucket" in text


def test_query_waits_for_device_attach(tmp_path):
    """A query arriving while open() is still attaching the device must
    not race the executor swap: it waits a bounded time for the attach,
    then gets 503 with Retry-After. The gate is keyed on the _mesh_ready
    event (unset from construction), so it also covers the window
    before the attach thread exists."""
    s = Server(Config(bind="127.0.0.1:0", data_dir=str(tmp_path / "d"),
                      anti_entropy_interval=0))
    s.ATTACH_WAIT_S = 0.1
    s.open()
    try:
        s._mesh_ready.clear()  # simulate an attach still in flight
        with pytest.raises(urllib.error.HTTPError) as e:
            call(s, "POST", "/index/x/query", b"Count(Row(f=1))")
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After")
        s._mesh_ready.set()
        # attached: the same query now dispatches (400 path, not
        # 503 — the index doesn't exist, which is the point: it got
        # PAST the gate)
        with pytest.raises(urllib.error.HTTPError) as e:
            call(s, "POST", "/index/x/query", b"Count(Row(f=1))")
        assert e.value.code == 400
    finally:
        s.close()


def test_explicit_zero_range_enforced(srv):
    """ADVICE r3: a field declared with range [0, 0] (only value 0
    legal) must enforce it — the 0/0 default means unbounded only when
    min/max were NOT provided."""
    import urllib.error

    call(srv, "POST", "/index/zr", {})
    call(srv, "POST", "/index/zr/field/v",
         {"options": {"type": "int", "min": 0, "max": 0}})
    call(srv, "POST", "/index/zr/field/v/import-value",
         {"columnIDs": [1], "values": [0]})  # legal
    try:
        call(srv, "POST", "/index/zr/field/v/import-value",
             {"columnIDs": [2], "values": [5]})
        raise AssertionError("out-of-range value accepted")
    except urllib.error.HTTPError as e:
        assert e.code == 400
    # unbounded when no range was declared
    call(srv, "POST", "/index/zr/field/u", {"options": {"type": "int"}})
    call(srv, "POST", "/index/zr/field/u/import-value",
         {"columnIDs": [1], "values": [123456]})


def test_old_schema_dump_restores_unbounded(srv):
    """Pre-hasRange /schema dumps serialize min:0/max:0 for unbounded
    int fields; restoring one must NOT enforce a [0, 0] range."""
    call(srv, "POST", "/schema", {"indexes": [{
        "name": "restored",
        "fields": [{"name": "v", "options": {"type": "int", "min": 0, "max": 0}}],
    }]})
    call(srv, "POST", "/index/restored/field/v/import-value",
         {"columnIDs": [1], "values": [999]})  # would 400 if [0,0] enforced
