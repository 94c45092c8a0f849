"""Event-driven serving front end (docs/serving.md).

Covers the tentpole contracts of the asyncio listener: HTTP/1.1
keep-alive multiplexing, bounded admission with 429/Retry-After
backpressure, admission-wait counting against the query deadline
(labeled 504, never executed), slow/abusive-client defenses (slowloris,
mid-body disconnect, oversized headers) with the loop staying live for
well-behaved traffic, the pooled keep-alive internal client, and the
429-backpressure classification in the resilience layer.  The
10k-concurrent-connection smoke test rides the ``slow`` marker.
"""

from __future__ import annotations

import http.client
import json
import socket
import ssl
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.parallel.client import InternalClient, PeerError
from pilosa_tpu.parallel.resilience import (
    BREAKER_CLOSED,
    BREAKER_OPEN,
    BreakerRegistry,
    ResilientClient,
    RetryPolicy,
)
from pilosa_tpu.server import Server
from pilosa_tpu.utils.config import Config

pytestmark = pytest.mark.serving


def make_server(tmp_path, **kw) -> Server:
    cfg = Config(
        bind="127.0.0.1:0",
        data_dir=str(tmp_path / "data"),
        anti_entropy_interval=0,
        **kw,
    )
    s = Server(cfg)
    s.open()
    s.wait_mesh(30)
    return s


@pytest.fixture
def srv(tmp_path):
    s = make_server(tmp_path)
    yield s
    s.close()


def call(srv, method, path, body=None, raw=False, headers=None):
    url = f"http://127.0.0.1:{srv.port}{path}"
    data = body if isinstance(body, (bytes, type(None))) else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=30) as resp:
        payload = resp.read()
        return payload if raw else json.loads(payload or b"{}")


def counters(srv) -> dict:
    return srv.stats.expvar()["counters"]


def seed_index(srv):
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    call(srv, "POST", "/index/i/query", b"Set(1, f=1) Set(3, f=1)")


# ------------------------------------------------------------- keep-alive
def test_keepalive_multiplexing_one_connection(srv):
    """Multiple requests ride ONE TCP connection; the server accepts
    exactly one connection for all of them."""
    seed_index(srv)
    before = counters(srv).get("connections_accepted", 0)
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
    try:
        for _ in range(5):
            conn.request("POST", "/index/i/query", b"Count(Row(f=1))")
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["results"] == [2]
    finally:
        conn.close()
    assert counters(srv).get("connections_accepted", 0) - before == 1


def test_connections_open_gauge(srv):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
    try:
        conn.request("GET", "/status")
        conn.getresponse().read()
        assert srv.stats.expvar()["gauges"]["connections_open"] >= 1
        v = call(srv, "GET", "/debug/vars")
        assert v["serving"]["mode"] == "event"
        assert v["serving"]["connectionsOpen"] >= 1
        assert set(v["serving"]["admission"]) == {"query", "write", "control"}
    finally:
        conn.close()


def test_idle_keepalive_reaped(tmp_path):
    """An idle keep-alive connection past keepalive-idle-s is closed by
    the server (silently — no response is owed between requests)."""
    s = make_server(tmp_path, keepalive_idle_s=0.3)
    try:
        conn = socket.create_connection(("127.0.0.1", s.port), timeout=5)
        conn.sendall(
            b"GET /status HTTP/1.1\r\nHost: x\r\nContent-Length: 0\r\n\r\n"
        )
        assert b"200" in conn.recv(65536)
        # idle now: the server reaps the connection after ~0.3s
        conn.settimeout(5)
        assert conn.recv(1) == b""  # FIN, no bytes
        conn.close()
    finally:
        s.close()


# ------------------------------------------------------- abusive clients
def test_slowloris_partial_head_times_out(tmp_path):
    """A client dribbling a partial request head is cut after
    request-read-timeout-s with 408 — while a concurrent well-behaved
    query keeps being served (the loop never blocks on the abuser)."""
    s = make_server(tmp_path, request_read_timeout_s=0.5)
    try:
        seed_index(s)
        abuser = socket.create_connection(("127.0.0.1", s.port), timeout=10)
        abuser.sendall(b"POST /index/i/query HTTP/1.1\r\nContent-Le")
        # the abuser is mid-head; well-behaved traffic must not notice
        t0 = time.perf_counter()
        r = call(s, "POST", "/index/i/query", b"Count(Row(f=1))")
        assert r["results"] == [2]
        assert time.perf_counter() - t0 < 5.0
        abuser.settimeout(5)
        answer = abuser.recv(65536)
        assert b"408" in answer
        abuser.close()
        assert counters(s)["queries_rejected{reason=header_timeout}"] >= 1
    finally:
        s.close()


def test_midbody_disconnect_leaves_loop_live(srv):
    seed_index(srv)
    bad = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
    bad.sendall(
        b"POST /index/i/query HTTP/1.1\r\nHost: x\r\n"
        b"Content-Length: 1000\r\n\r\npartial"
    )
    bad.close()  # mid-body hangup
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if counters(srv).get("connections_aborted_midbody", 0) >= 1:
            break
        time.sleep(0.02)
    assert counters(srv).get("connections_aborted_midbody", 0) >= 1
    # the loop is intact: a normal query still serves
    assert call(srv, "POST", "/index/i/query", b"Count(Row(f=1))")["results"] == [2]


def test_oversized_header_rejected(srv):
    seed_index(srv)
    bad = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
    bad.sendall(b"GET /status HTTP/1.1\r\n")
    junk = b"X-Filler: " + b"a" * 8000 + b"\r\n"
    try:
        for _ in range(12):  # ~96 KiB of headers, past the 64 KiB cap
            bad.sendall(junk)
    except OSError:
        pass  # server may reset mid-send; the response check below decides
    bad.settimeout(5)
    try:
        answer = bad.recv(65536)
        assert not answer or b"431" in answer
    except OSError:
        pass
    bad.close()
    assert counters(srv)["queries_rejected{reason=header_too_large}"] >= 1
    assert call(srv, "GET", "/status")["state"] == "NORMAL"


def test_conflicting_content_length_rejected(srv):
    """Two Content-Length headers with different values: the loop must
    refuse rather than frame by one while a downstream parser honors
    the other — the request-smuggling split on a keep-alive socket."""
    seed_index(srv)
    bad = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
    bad.sendall(
        b"POST /index/i/query HTTP/1.1\r\nHost: x\r\n"
        b"Content-Length: 10\r\nContent-Length: 60\r\n\r\n"
        b"Count(Row("
    )
    bad.settimeout(5)
    answer = bad.recv(65536)
    assert b"400" in answer and b"Content-Length" in answer
    bad.close()
    assert counters(srv)["queries_rejected{reason=bad_request}"] >= 1
    assert call(srv, "POST", "/index/i/query", b"Count(Row(f=1))")["results"] == [2]


def test_deadline_only_governs_query_class(srv):
    """An exhausted deadline header on a control route must not 504 at
    admission: on the threaded path the budget governed query routes
    alone, and a busy-but-alive node's /status heartbeats dying in the
    control lane would cause the exact dead-marking the per-class
    admission lanes exist to prevent."""
    out = call(srv, "GET", "/status", headers={"X-Pilosa-Deadline-Ms": "0"})
    assert out["state"] == "NORMAL"


# -------------------------------------------------------------- admission
def _blocking_router(resp=None):
    """A query router that parks until released, recording entries."""
    started = threading.Event()
    release = threading.Event()
    calls = []

    def router(index, pql, shards):
        calls.append(pql)
        started.set()
        release.wait(10)
        return resp or {"results": [0]}

    return router, started, release, calls


def test_admission_queue_full_429(tmp_path):
    """query-class concurrency 1 + queue depth 1: with one query
    executing and one queued, the next gets 429 + Retry-After without
    executing — and control routes keep serving throughout."""
    s = make_server(tmp_path, http_worker_threads=1, admission_queue_depth=1)
    try:
        seed_index(s)
        router, started, release, calls = _blocking_router()
        s.http.query_router = router
        results = {}

        def client(name):
            try:
                results[name] = call(s, "POST", "/index/i/query", b"Count(Row(f=1))")
            except urllib.error.HTTPError as e:
                results[name] = (e.code, e.headers.get("Retry-After"), e.read())

        t1 = threading.Thread(target=client, args=("first",))
        t1.start()
        assert started.wait(10)
        t2 = threading.Thread(target=client, args=("second",))
        t2.start()
        # wait until the second query is visibly queued
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            adm = call(s, "GET", "/debug/vars")["serving"]["admission"]
            if adm["query"]["queueDepth"] >= 1:
                break
            time.sleep(0.02)
        assert adm["query"]["queueDepth"] >= 1
        # queue is full: the third client is shed at the door
        client("third")
        code, retry_after, body = results["third"]
        assert code == 429 and retry_after is not None
        assert b"admission queue full" in body
        assert counters(s)["queries_rejected{reason=queue_full}"] >= 1
        release.set()
        t1.join(10)
        t2.join(10)
        assert results["first"]["results"] == [0]
        assert results["second"]["results"] == [0]
        assert len(calls) == 2  # the rejected query never executed
    finally:
        s.close()


def test_deadline_spent_in_queue_is_labeled_504(tmp_path):
    """A query whose X-Pilosa-Deadline-Ms budget dies while it waits in
    admission returns the labeled 504 and NEVER executes."""
    s = make_server(tmp_path, http_worker_threads=1)
    try:
        seed_index(s)
        router, started, release, calls = _blocking_router()
        s.http.query_router = router
        result = {}

        def blocker():
            result["first"] = call(s, "POST", "/index/i/query", b"Count(Row(f=1))")

        t1 = threading.Thread(target=blocker)
        t1.start()
        assert started.wait(10)

        def doomed():
            try:
                result["doomed"] = call(
                    s, "POST", "/index/i/query", b"Count(Row(f=1))",
                    headers={"X-Pilosa-Deadline-Ms": "100"},
                )
            except urllib.error.HTTPError as e:
                result["doomed"] = (e.code, e.read())

        t2 = threading.Thread(target=doomed)
        t2.start()
        time.sleep(0.4)  # > the 100ms budget, while still queued
        release.set()
        t1.join(10)
        t2.join(10)
        code, body = result["doomed"]
        assert code == 504
        assert b"deadline exceeded" in body and b"admission queue" in body
        assert counters(s)["queries_rejected{reason=deadline}"] >= 1
        assert len(calls) == 1  # only the blocker executed
    finally:
        s.close()


def test_max_connections_cap(tmp_path):
    s = make_server(tmp_path, max_connections=1)
    try:
        keeper = http.client.HTTPConnection("127.0.0.1", s.port, timeout=10)
        keeper.request("GET", "/status")
        first = keeper.getresponse()
        assert first.status == 200
        first.read()  # drain: keep-alive reuse needs the body consumed
        extra = http.client.HTTPConnection("127.0.0.1", s.port, timeout=10)
        extra.request("GET", "/status")
        resp = extra.getresponse()
        assert resp.status == 503
        assert resp.getheader("Retry-After") is not None
        resp.read()
        extra.close()
        assert counters(s)["queries_rejected{reason=max_connections}"] >= 1
        # the original connection is unaffected
        keeper.request("GET", "/status")
        again = keeper.getresponse()
        assert again.status == 200
        again.read()
        keeper.close()
    finally:
        s.close()


def test_admission_metrics_populated(srv):
    seed_index(srv)
    call(srv, "POST", "/index/i/query", b"Count(Row(f=1))")
    ev = srv.stats.expvar()
    assert any(
        k.startswith("admission_wait_seconds") for k in ev["timings"]
    )
    assert any(
        k.startswith("admission_queue_depth") for k in ev.get("distributions", {})
    )


# --------------------------------------------- pooled internal transport
def test_internal_client_pools_keepalive_connections(srv):
    uri = f"http://127.0.0.1:{srv.port}"
    c = InternalClient(timeout=10)
    before = counters(srv).get("connections_accepted", 0)
    for _ in range(4):
        assert c.status(uri)["state"] == "NORMAL"
    assert counters(srv).get("connections_accepted", 0) - before == 1
    assert c._pool.snapshot() == {uri: 1}
    # breaker-open style eviction drops the pooled socket; the next RPC
    # dials fresh
    c.evict_peer(uri)
    assert c._pool.snapshot() == {}
    assert c.status(uri)["state"] == "NORMAL"
    assert counters(srv).get("connections_accepted", 0) - before == 2
    c.close()


def test_transport_failure_leaves_no_pooled_connections():
    c = InternalClient(timeout=0.5)
    with pytest.raises(PeerError):
        c.status("http://127.0.0.1:1")
    assert c._pool.snapshot() == {}


def test_peer_429_is_backpressure_not_breaker_failure():
    """A peer's admission-queue 429 is non-retryable-with-backoff: no
    in-query retry, retry_after surfaced, breaker stays CLOSED."""

    class Shedding:
        def __init__(self):
            self.calls = 0

        def query_node(self, uri, *a, **k):
            self.calls += 1
            raise PeerError(
                uri, "HTTP 429: admission queue full", status=429,
                retry_after=1.5,
            )

    inner = Shedding()
    breakers = BreakerRegistry(threshold=2, cooldown_s=60.0)
    rc = ResilientClient(
        inner, breakers, RetryPolicy(retries=3, sleep=lambda s: None)
    )
    uri = "http://peer:1"
    for _ in range(5):
        with pytest.raises(PeerError) as e:
            rc.query_node(uri, "i", "Count(Row(f=1))", None)
        assert e.value.backpressure and not e.value.retryable
        assert e.value.retry_after == 1.5
    assert inner.calls == 5  # one attempt per call: never retried in-query
    assert breakers.get(uri).state == BREAKER_CLOSED


def test_breaker_open_evicts_peer_pool():
    """When consecutive failures OPEN a peer's breaker, the resilience
    layer evicts the transport's pooled connections for that peer."""

    class Dead:
        def __init__(self):
            self.evicted = []

        def query_node(self, uri, *a, **k):
            raise PeerError(uri, "connection refused")

        def evict_peer(self, uri):
            self.evicted.append(uri)

    inner = Dead()
    breakers = BreakerRegistry(threshold=2, cooldown_s=60.0)
    rc = ResilientClient(
        inner, breakers, RetryPolicy(retries=0, sleep=lambda s: None)
    )
    uri = "http://peer:1"
    for _ in range(2):
        with pytest.raises(PeerError):
            rc.query_node(uri, "i", "Count(Row(f=1))", None)
    assert breakers.get(uri).state == BREAKER_OPEN
    assert inner.evicted == [uri]


# ---------------------------------------------------------------- shutdown
def _http_threads(s) -> list:
    """This server's live listener threads: its loop and its workers."""
    pool = s.http._pool
    threads = [s.http._thread, *(pool._threads if pool is not None else ())]
    return [t for t in threads if t is not None and t.is_alive()]


def _assert_closes(s, within: float = 2.0) -> None:
    port = s.port
    t0 = time.monotonic()
    s.close()
    took = time.monotonic() - t0
    assert took < within, f"Server.close() took {took:.2f}s"
    deadline = time.monotonic() + 1.0
    while _http_threads(s) and time.monotonic() < deadline:
        time.sleep(0.01)  # an idle worker leaves on its pool's signal
    assert not _http_threads(s)
    with pytest.raises(ConnectionRefusedError):
        socket.create_connection(("127.0.0.1", port), timeout=2)


def _idle_keepalive(port, wrap=lambda sock: sock):
    conn = wrap(socket.create_connection(("127.0.0.1", port), timeout=5))
    conn.sendall(b"GET /status HTTP/1.1\r\nHost: x\r\nContent-Length: 0\r\n\r\n")
    assert b"200" in conn.recv(65536)
    return conn


@pytest.mark.filterwarnings("error::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize(
    "case",
    ["no_connection", "idle_keepalive", "half_head", "in_flight",
     "tls_idle_keepalive", "cluster_peers", "handler_never_returns",
     "slow_reader", "reader_never_reads", "tls_handshake_in_flight"],
)
def test_close_stops_the_listener(tmp_path, certpair, monkeypatch, case):
    """``Server.close()`` returns at once with the loop thread and the
    workers dead and the port refusing, whatever the clients hold open
    (docs/serving.md "Shutdown"): a connection that owes nothing is
    dropped, a request in flight gets its whole reply first.  The one
    thing that may not be waited out, a handler that never returns,
    makes it raise instead of closing the holder under a live worker."""
    from pilosa_tpu.server import eventloop

    if case == "cluster_peers":
        from tests.test_cluster import call as ccall, make_cluster

        servers, ports, _ = make_cluster(tmp_path, n=2, replica_n=2)
        ccall(ports[0], "POST", "/index/i", {})
        ccall(ports[0], "POST", "/index/i/field/f", {})
        # both directions: each node's pooled client holds a
        # keep-alive connection to the other
        for p in ports:
            ccall(p, "POST", "/index/i/query", b"Set(1, f=1)")
            assert ccall(p, "POST", "/index/i/query", b"Count(Row(f=1))") == {
                "results": [1]
            }
        assert all(s.http._conn_count >= 1 for s in servers)
        for s in servers:
            _assert_closes(s)
        return
    kw = {}
    if case.startswith("tls_"):
        kw = dict(tls_certificate=certpair[0], tls_key=certpair[1])
    s = make_server(tmp_path, **kw)
    logged = []
    log = s.http.log
    s.http.log = lambda msg: (logged.append(msg), log(msg))
    held = []
    try:
        if case == "idle_keepalive":
            held.append(_idle_keepalive(s.port))
        elif case.startswith("tls_"):
            if case == "tls_handshake_in_flight":
                # TCP open, no ClientHello: a connection the listener
                # counts and the loop never sees.  Accepted for sure
                # once the one behind it has been answered
                mute = socket.create_connection(("127.0.0.1", s.port), timeout=5)
            ctx = ssl.create_default_context(cafile=certpair[0])
            held.append(_idle_keepalive(
                s.port, lambda k: ctx.wrap_socket(k, server_hostname="127.0.0.1")
            ))
            if case == "tls_handshake_in_flight":
                held.append(mute)  # closed too, not left to its timeout
        elif case == "half_head":
            conn = socket.create_connection(("127.0.0.1", s.port), timeout=5)
            conn.sendall(b"GET /status HTTP/1.1\r\nHost: x\r\n")
            held.append(conn)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and not any(
                c.phase == eventloop._ConnState.HEAD for c in s.http._conns
            ):
                time.sleep(0.01)
        elif case == "in_flight":
            seed_index(s)
            started = threading.Event()

            def slow(index, pql, shards):
                started.set()
                time.sleep(0.3)
                return {"results": [7]}

            s.http.query_router = slow
            conn = http.client.HTTPConnection("127.0.0.1", s.port, timeout=10)
            conn.request("POST", "/index/i/query", b"Count(Row(f=1))")
            assert started.wait(5)
            _assert_closes(s)
            # acknowledged or cut, never half: the whole reply, then EOF
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read()) == {"results": [7]}
            assert conn.sock is None or conn.sock.recv(1) == b""
            conn.close()
            return
        elif case == "handler_never_returns":
            seed_index(s)
            router, started, release, _ = _blocking_router()
            s.http.query_router = router
            monkeypatch.setattr(eventloop, "SHUTDOWN_GRACE_S", 0.3)
            holder_closes = []
            close_holder = s.holder.close
            monkeypatch.setattr(
                s.holder, "close",
                lambda: (holder_closes.append(1), close_holder()),
            )
            t = threading.Thread(
                target=lambda: pytest.raises(
                    Exception, call, s, "POST", "/index/i/query", b"Count(Row(f=1))"
                )
            )
            t.start()
            assert started.wait(5)
            # read from live state: however often it is asked, close()
            # refuses while the worker runs, and has closed nothing but
            # the listener ...
            for _ in range(2):
                with pytest.raises(RuntimeError, match=r"1 admitted request.*still running"):
                    s.close()
                assert not holder_closes and not s._closed
                assert s.api.query("i", "Count(Row(f=1))") == {"results": [2]}
            assert any("cut after 0.3s, by phase: ['busy']" in m for m in logged)
            release.set()
            t.join(5)
            assert not t.is_alive()
            deadline = time.monotonic() + 5
            while not all(w.done() for w in s.http._cut):
                assert time.monotonic() < deadline
                time.sleep(0.01)
            _assert_closes(s)  # ... and the rest, once the worker has gone
            assert holder_closes
            return
        elif case in ("slow_reader", "reader_never_reads"):
            # a reply larger than the socket buffers, and a client that
            # reads none of it until the close is under way: owed whole.
            # One that never reads is cut at the bound, and that is no
            # fault of the node's: nothing runs, close() goes through
            seed_index(s)
            big = "x" * (16 << 20)
            s.http.query_router = lambda index, pql, shards: {"results": [big]}
            conn = socket.socket()
            conn.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 65536)
            conn.settimeout(10)
            conn.connect(("127.0.0.1", s.port))
            q = b"Count(Row(f=1))"
            conn.sendall(
                b"POST /index/i/query HTTP/1.1\r\nHost: x\r\n"
                b"Content-Length: %d\r\n\r\n%s" % (len(q), q)
            )
            deadline = time.monotonic() + 10
            while not any(
                c.writer.transport.get_write_buffer_size() for c in s.http._conns
            ):
                assert time.monotonic() < deadline  # the kernel took it all?
                time.sleep(0.01)
            if case == "reader_never_reads":
                monkeypatch.setattr(eventloop, "SHUTDOWN_GRACE_S", 0.3)
                _assert_closes(s, within=3.0)  # the grace and a second
                conn.close()
                assert any("by phase: ['busy']" in m for m in logged)
                assert any("1 replies not read to their end" in m for m in logged)
                return
            got = bytearray()

            def read_late():
                time.sleep(0.3)
                while chunk := conn.recv(1 << 20):
                    got.extend(chunk)

            t = threading.Thread(target=read_late)
            t.start()
            _assert_closes(s)
            t.join(10)
            assert not t.is_alive()  # the reply, then EOF
            conn.close()
            head, _, body = bytes(got).partition(b"\r\n\r\n")
            assert head.startswith(b"HTTP/1.1 200")
            assert json.loads(body) == {"results": [big]}
            return
        _assert_closes(s)
        for conn in held:
            assert conn.recv(1) == b""  # dropped: nothing was owed
    finally:
        for conn in held:
            conn.close()


# ------------------------------------------------------------- 10k smoke
@pytest.mark.slow
def test_10k_concurrent_connections_smoke(tmp_path):
    """10k held-open connections (two child processes × 5k, so client
    FDs don't eat this process's limit) while queries keep serving:
    p99 stays steady and the event loop records zero unhandled
    exceptions."""
    import subprocess
    import sys

    s = make_server(tmp_path)
    try:
        seed_index(s)
        child_src = (
            "import socket, sys\n"
            "host, port, n = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])\n"
            "socks = []\n"
            "for _ in range(n):\n"
            "    try:\n"
            "        socks.append(socket.create_connection((host, port), timeout=30))\n"
            "    except OSError:\n"
            "        break\n"
            "print(len(socks), flush=True)\n"
            "sys.stdin.readline()\n"
            "for sk in socks:\n"
            "    sk.close()\n"
        )
        children = [
            subprocess.Popen(
                [sys.executable, "-c", child_src, "127.0.0.1", str(s.port), "5000"],
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                text=True,
            )
            for _ in range(2)
        ]
        try:
            held = sum(int(ch.stdout.readline()) for ch in children)
            assert held >= 9800, f"only {held} connections held"
            # queries keep serving under 10k idle connections
            lats = []
            for _ in range(60):
                t0 = time.perf_counter()
                r = call(s, "POST", "/index/i/query", b"Count(Row(f=1))")
                lats.append(time.perf_counter() - t0)
                assert r["results"] == [2]
            lats.sort()
            p99 = lats[int(len(lats) * 0.99) - 1]
            assert p99 < 2.0, f"p99 {p99:.3f}s under 10k connections"
            ev = s.stats.expvar()
            assert ev["gauges"]["connections_open"] >= held
            assert ev["counters"].get("eventloop_unhandled_exceptions", 0) == 0
        finally:
            for ch in children:
                try:
                    ch.stdin.write("\n")
                    ch.stdin.flush()
                except OSError:
                    pass
                ch.wait(timeout=30)
    finally:
        s.close()
