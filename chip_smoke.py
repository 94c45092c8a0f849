"""chip_smoke.py — the quickest proof that the served path runs on the chip.

    python3 chip_smoke.py            # one TPU chip
    python3 chip_smoke.py --mesh     # one server over four chips (mesh route)

Starts ``python -m pilosa_tpu server`` as its ONE child (the only process
that touches the chip: this process never imports jax), loads a seeded
taxi-shaped index over the public bulk route at the production shard
width, and drives ``POST /index/{i}/query`` → event loop → router → wave
scheduler → device engine → readback. Every answer is compared bit for
bit with a numpy reference computed here from the same seeded arrays;
acknowledged ``Set``/``Clear`` are read back before and after a restart on
the same data dir; ``/metrics`` must show the device engine served every
read; a second, over-budget index drives the tiered container kernels.

One JSON object per phase goes to stdout. The LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
with the device as the server's ``GET /info`` reports it; any phase that
fails raises, so the run ends non-zero and that line is never printed.
With no accelerator the server reports platform ``cpu`` and the run
fails before loading anything.

Shape (upstream Pilosa's "Transportation" example, BASELINE config 3):
``cab_type`` and ``passenger_count`` set fields, ``fare`` a 16-bit BSI
int field in cents, every column carrying one value of each. The value
distributions are assumed (skewed, seeded), not the real trip data.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

import numpy as np

from pilosa_tpu import loader, native, roaring
from pilosa_tpu.shardwidth import SHARD_WIDTH

REPO = os.path.dirname(os.path.abspath(__file__))

# the upstream demo's scale: 954 shards = 1.0 B columns, ~119 MiB per row
DEMO_SHARDS = 954
# what this run loads, and why it is cut from DEMO_SHARDS (PERF.md,
# Findings, PR 21). Time: the run is host-bound and grows with the shard
# count — 340 s at 256 shards on the chip machine, so 954 projects past
# 1100 s of the 1200 s allowed. Memory: at 512 shards the run got as far
# as the re-reads and then GroupBy(aggregate=Sum) failed
# RESOURCE_EXHAUSTED, its 3 GB of program temporaries not fitting beside
# 3.8 GB of stacks and the 5.9 GB container stores of the second index.
SHARDS = 256
SHARDS_CUT_REASON = (
    "954 shards project past 1100 s of the 1200 s limit (host-bound load "
    "and stack packing); 512 ran out of HBM in GroupBy(aggregate=Sum) "
    "beside the tiered stores; 256 is the floor ISSUE 21 allows"
)
RESIDENCY_SHARDS = 64
DEFAULT_SEED = 20260926

INDEX = "taxi"
# row id → share of columns, in 1/256ths (a byte of the seeded stream
# picks the row through a lookup table)
CAB_SHARES = (141, 77, 26, 10, 2)
PASSENGER_SHARES = (3, 179, 36, 13, 8, 10, 5, 2)
FARE_BITS = 16
BSI_EXISTS, BSI_OFFSET = 0, 2  # bsi view rows: 0 exists, 1 sign, 2.. bits

RARE_INDEX = "rare"
RARE_FIELD = "tag"
# over-budget field: the dense stack is sized by the HIGHEST row id, so a
# few rows spread to 2047 project to [2048, S, W] — 16 GiB at 64 shards
RARE_SPARSE_ROWS = (7, 1033, 2047)  # ≤ 2048 bits each → sparse container
RARE_RUN_ROWS = (64, 1500)  # 64 intervals of 8192 bits → run container
RARE_DENSE_ROW = 300  # 0.5 % random bits → stays a dense plane


class SmokeFailure(AssertionError):
    pass


_T0 = time.monotonic()


def emit(phase: str, **fields) -> None:
    """One JSON line per phase; ``t`` is seconds since the script began."""
    print(json.dumps({"phase": phase, "t": round(time.monotonic() - _T0, 1),
                      **fields}), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------------ server
class ServerProcess:
    """The one child: ``python -m pilosa_tpu server`` over a data dir."""

    def __init__(self, workdir: str, name: str, config_path: str, data_dir: str):
        self.log_path = os.path.join(workdir, f"{name}.log")
        self.config_path = config_path
        self.data_dir = data_dir
        self.proc: subprocess.Popen | None = None
        self.base = ""
        self.spawned_at = 0.0
        self.listening_s = 0.0  # spawn → first HTTP answer

    def start(self, ready_timeout: float = 300.0) -> None:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{port}"
        # JAX_LOG_COMPILES: the tree has no compile counter; its log
        # lines are counted after the boot (compile_counts)
        env = dict(os.environ, JAX_LOG_COMPILES="1")
        self.spawned_at = time.monotonic()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "pilosa_tpu", "server",
                 "--bind", f"127.0.0.1:{port}",
                 "--data-dir", self.data_dir,
                 "--config", self.config_path],
                cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
            )
        deadline = time.monotonic() + ready_timeout
        while True:
            check(self.proc.poll() is None,
                  f"server exited {self.proc.returncode} during boot:\n"
                  + self.log_tail())
            try:
                if not self.listening_s:
                    http_json(self.base + "/status", timeout=5)
                    self.listening_s = self.since_spawn()
                # the listener answers before Server.open() has finished;
                # /info carries its diagnostics block once it has
                if "diagnostics" in http_json(self.base + "/info"):
                    return
            except (urllib.error.URLError, OSError):
                pass
            check(time.monotonic() < deadline,
                  f"server not answering after {ready_timeout:.0f}s:\n"
                  + self.log_tail())
            time.sleep(0.1)

    def since_spawn(self) -> float:
        return time.monotonic() - self.spawned_at

    def stop(self) -> None:
        """SIGTERM, then require a clean exit."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=180)
        except subprocess.TimeoutExpired:
            self.kill()
            raise SmokeFailure("server ignored SIGTERM for 180s") from None
        check(rc == 0, f"server exited {rc} on SIGTERM:\n" + self.log_tail())

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def log_tail(self, n: int = 30) -> str:
        if not os.path.exists(self.log_path):
            return ""
        with open(self.log_path, errors="replace") as f:
            # JAX_LOG_COMPILES lines are most of the log and say little
            lines = [x for x in f if "Finished " not in x and "Compiling " not in x]
        return "".join(x[:400] for x in lines[-n:])

    def compile_counts(self) -> dict:
        """Compilations of this boot from JAX's own log: every backend
        compile request logs 'Finished XLA compilation'; the ones the
        persistent cache answered also log a cache hit."""
        with open(self.log_path, errors="replace") as f:
            text = f.read()
        requests = text.count("Finished XLA compilation of")
        hits = text.count("Persistent compilation cache hit for")
        # the same program name compiled again for the same argument
        # shapes and shardings (a hint of a double compile; two programs
        # that share a name and shapes also land here)
        signatures = [
            line.split("Compiling ", 1)[1]
            for line in text.splitlines()
            if "Compiling " in line and "with global shapes" in line
        ]
        return {"compile_requests": requests, "persistent_cache_hits": hits,
                "compiled": requests - hits,
                "repeated_signatures": len(signatures) - len(set(signatures))}


def http_json(url: str, body: bytes | None = None, timeout: float = 600.0):
    req = urllib.request.Request(
        url, data=body, method="POST" if body is not None else "GET"
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        detail = e.read()[:500]
        raise SmokeFailure(f"{url}: HTTP {e.code} {detail!r}") from None


def query(base: str, index: str, pql: str, params: str = ""):
    out = http_json(f"{base}/index/{index}/query{params}", pql.encode())
    check("error" not in out, f"{pql}: {out.get('error')}")
    return out


def metrics(base: str, family: str) -> dict:
    """Samples of one /metrics family: {label string: value}."""
    with urllib.request.urlopen(base + "/metrics", timeout=60) as resp:
        text = resp.read().decode()
    out = {}
    for line in text.splitlines():
        key, _, value = line.rpartition(" ")
        name, _, labels = key.partition("{")
        if name == f"pilosa_tpu_{family}":
            out[labels.rstrip("}")] = float(value)
    return out


def routed(base: str) -> dict:
    """queries_routed{path=...}: read calls per engine."""
    out = {"host": 0, "device": 0, "mesh": 0}
    for labels, value in metrics(base, "queries_routed").items():
        out[labels.split('"')[1]] = int(value)
    return out


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# -------------------------------------------------------------------- data
def _lookup(shares) -> np.ndarray:
    check(sum(shares) == 256, "row shares must sum to 256")
    return np.repeat(np.arange(len(shares), dtype=np.uint8), shares)


_CAB_LUT = _lookup(CAB_SHARES)
_PASSENGER_LUT = _lookup(PASSENGER_SHARES)


def gen_shard(seed: int, shard: int):
    """The seeded columns of one shard: (cab uint8, passengers uint8,
    fare uint16 cents). Two seeded draws per column."""
    rng = np.random.default_rng([seed, shard])
    x = rng.integers(0, 1 << 32, SHARD_WIDTH, dtype=np.uint32)
    y = rng.integers(0, 1 << 16, SHARD_WIDTH, dtype=np.uint32)
    cab = _CAB_LUT[x & 0xFF]
    passengers = _PASSENGER_LUT[(x >> 8) & 0xFF]
    # product of two uniform 16-bit draws: skewed low, every bit used
    fare = (((x >> 16) * y) >> 16).astype(np.uint16)
    return cab, passengers, fare


def _pack(mask: np.ndarray) -> np.ndarray:
    return np.packbits(mask, bitorder="little").view(np.uint32)


def shard_frames(shard: int, cab, passengers, fare):
    """The three import-roaring frames of one shard, built from dense
    packed rows (roaring.payload_from_rows)."""
    yield ("cab_type", "standard", shard) + roaring.payload_from_rows(
        (r, _pack(cab == r)) for r in range(len(CAB_SHARES))
    )
    yield ("passenger_count", "standard", shard) + roaring.payload_from_rows(
        (r, _pack(passengers == r)) for r in range(len(PASSENGER_SHARES))
    )
    slices = [(BSI_EXISTS, _pack(np.ones(SHARD_WIDTH, dtype=bool)))]
    slices += [
        (BSI_OFFSET + b, _pack(((fare >> b) & 1).astype(bool)))
        for b in range(FARE_BITS)
    ]
    yield ("fare", "bsi", shard) + roaring.payload_from_rows(slices)


# --------------------------------------------------------------- reference
class ShardView:
    """One shard's columns as the reference sees them: the seeded arrays
    plus the acknowledged writes that landed in this shard. A set field
    is a byte per column whose bit r says "this column is in row r" (a
    Set can put a column in two rows). Uses nothing of pilosa_tpu's
    executor or ops."""

    def __init__(self, cab, passengers, fare, writes=()):
        self._member = {
            "cab_type": np.uint8(1) << cab,
            "passenger_count": np.uint8(1) << passengers,
        }
        self.fare = fare.astype(np.int64)
        for kind, field, value, pos in writes:
            if field == "fare":
                self.fare[pos] = value
            elif kind == "set":
                self._member[field][pos] |= 1 << value
            else:
                self._member[field][pos] &= ~(1 << value) & 0xFF

    def row(self, field: str, row: int) -> np.ndarray:
        """bool[SHARD_WIDTH]: the columns in this row."""
        return (self._member[field] >> row) & 1 == 1

    def joint(self, weights=None) -> np.ndarray:
        """[cab, passengers] column counts (or sums of ``weights``): one
        histogram over the (cab byte, passenger byte) pairs, folded by
        which rows each byte value is a member of. float64 is exact
        here (every partial sum is below 2^53)."""
        pair = (self._member["cab_type"].astype(np.intp) << 8) | self._member[
            "passenger_count"
        ]
        hist = np.bincount(pair, weights=weights, minlength=1 << 16)
        byte = np.arange(256)
        in_cab = (byte[None] >> np.arange(len(CAB_SHARES))[:, None]) & 1
        in_pas = (byte[None] >> np.arange(len(PASSENGER_SHARES))[:, None]) & 1
        cells = in_cab @ hist.reshape(256, 256).astype(np.float64) @ in_pas.T
        return np.rint(cells).astype(np.int64)


def _merge_extreme(better):
    def merge(a, b):
        if a is None or b is None:
            return a if b is None else b
        if a[0] == b[0]:
            return (a[0], a[1] + b[1])
        return a if better(a[0], b[0]) else b
    return merge


def _add(a, b):
    return b if a is None else a + b


def _extreme(values: np.ndarray, pick):
    v = int(pick(values))
    return (v, int((values == v).sum()))


def _pairs(counts) -> list[dict]:
    order = sorted(range(len(counts)), key=lambda r: (-int(counts[r]), r))
    return [{"id": r, "count": int(counts[r])} for r in order if counts[r] > 0]


def _groups(counts, sums=None) -> list[dict]:
    out = []
    for c in range(counts.shape[0]):
        for p in range(counts.shape[1]):
            if counts[c, p] == 0:
                continue
            g = {
                "group": [
                    {"field": "cab_type", "rowID": c},
                    {"field": "passenger_count", "rowID": p},
                ],
                "count": int(counts[c, p]),
            }
            if sums is not None:
                g["sum"] = int(sums[c, p])
            out.append(g)
    return out


def _row_counts(v: ShardView, field: str, n: int, filt=None) -> np.ndarray:
    return np.array(
        [
            (v.row(field, r) if filt is None else v.row(field, r) & filt).sum()
            for r in range(n)
        ],
        dtype=np.int64,
    )


def _sum_count(v: ShardView, mask=None) -> np.ndarray:
    vals = v.fare if mask is None else v.fare[mask]
    return np.array([vals.sum(), vals.size], dtype=np.int64)


def _value_count(acc) -> dict:
    return {"value": int(acc[0]), "count": int(acc[1])}


# per-shard partial answer, merge of two partials, finish → the JSON result
Read = collections.namedtuple("Read", "name pql partial merge finish")
READS = [Read(*spec) for spec in [
    ("count_intersect",
     "Count(Intersect(Row(cab_type=0), Row(passenger_count=1)))",
     lambda v: int((v.row("cab_type", 0) & v.row("passenger_count", 1)).sum()),
     _add, int),
    ("count_union",
     "Count(Union(Row(cab_type=1), Row(cab_type=3), Row(passenger_count=4)))",
     lambda v: int((v.row("cab_type", 1) | v.row("cab_type", 3)
                    | v.row("passenger_count", 4)).sum()),
     _add, int),
    ("count_difference",
     "Count(Difference(Row(cab_type=0), Row(passenger_count=1)))",
     lambda v: int((v.row("cab_type", 0) & ~v.row("passenger_count", 1)).sum()),
     _add, int),
    ("count_not",  # every column exists, so Not() is the plain complement
     "Count(Not(Row(cab_type=0)))",
     lambda v: int((~v.row("cab_type", 0)).sum()),
     _add, int),
    ("topn", "TopN(cab_type, n=3)",
     lambda v: _row_counts(v, "cab_type", len(CAB_SHARES)),
     _add, lambda acc: _pairs(acc)[:3]),
    ("topn_filtered", "TopN(passenger_count, Row(cab_type=1), n=4)",
     lambda v: _row_counts(v, "passenger_count", len(PASSENGER_SHARES),
                           v.row("cab_type", 1)),
     _add, lambda acc: _pairs(acc)[:4]),
    ("sum", "Sum(field=fare)", _sum_count, _add, _value_count),
    ("sum_filtered", "Sum(Row(cab_type=2), field=fare)",
     lambda v: _sum_count(v, v.row("cab_type", 2)), _add, _value_count),
    ("min", "Min(field=fare)",
     lambda v: _extreme(v.fare, np.min),
     _merge_extreme(lambda a, b: a < b), _value_count),
    ("max", "Max(field=fare)",
     lambda v: _extreme(v.fare, np.max),
     _merge_extreme(lambda a, b: a > b), _value_count),
    ("range_gt", "Count(Row(fare > 20000))",
     lambda v: int((v.fare > 20000).sum()), _add, int),
    ("range_between", "Count(Row(1000 <= fare <= 30000))",
     lambda v: int(((v.fare >= 1000) & (v.fare <= 30000)).sum()), _add, int),
    ("groupby", "GroupBy(Rows(cab_type), Rows(passenger_count))",
     ShardView.joint, _add, _groups),
    ("groupby_sum",
     "GroupBy(Rows(cab_type), Rows(passenger_count), "
     "aggregate=Sum(field=fare))",
     lambda v: np.stack([v.joint(), v.joint(v.fare)]),
     _add, lambda acc: _groups(acc[0], acc[1])),
]]
# re-read after the writes (before and after the restart): every answer
# here moves with at least one of the writes below
REREADS = ("count_intersect", "count_union", "topn", "sum", "sum_filtered",
           "max", "range_gt", "groupby_sum")


class Reference:
    """Per-shard partial answers of every READS entry, merged on demand;
    a write replaces the partials of the one shard it touched."""

    def __init__(self, seed: int):
        self.seed = seed
        self.partials: dict[int, list] = {}
        self.writes: dict[int, list] = {}

    def add_shard(self, shard: int, cab, passengers, fare) -> None:
        view = ShardView(cab, passengers, fare, self.writes.get(shard, ()))
        self.partials[shard] = [q.partial(view) for q in READS]

    def apply_write(self, kind: str, field: str, value: int, col: int) -> None:
        shard, pos = divmod(col, SHARD_WIDTH)
        self.writes.setdefault(shard, []).append((kind, field, value, pos))
        self.add_shard(shard, *gen_shard(self.seed, shard))

    def expected(self, name: str):
        i, read = next((k, q) for k, q in enumerate(READS) if q.name == name)
        acc = None
        for shard in sorted(self.partials):
            acc = read.merge(acc, self.partials[shard][i])
        return read.finish(acc)


# ------------------------------------------------------------------ phases
def device_facts(base: str, expect_platform: str, expect_count: int | None):
    diag = http_json(base + "/info")["diagnostics"]
    facts = {"platform": diag["backend"], "kind": diag["device_kind"],
             "count": diag["device_count"]}
    check(facts["platform"] == expect_platform,
          f"server runs on {facts['platform']!r}, need {expect_platform!r}: "
          f"{diag}")
    check(expect_count is None or facts["count"] == expect_count,
          f"server sees {facts['count']} devices, need {expect_count}")
    check(not diag["router_pinned_host"], "router is pinned to the host")
    return facts, diag


def load_taxi(base: str, seed: int, n_shards: int, ref: Reference) -> dict:
    http_json(f"{base}/index/{INDEX}", b"{}")
    http_json(f"{base}/index/{INDEX}/field/cab_type", b"{}")
    http_json(f"{base}/index/{INDEX}/field/passenger_count", b"{}")
    http_json(
        f"{base}/index/{INDEX}/field/fare",
        json.dumps({"options": {"type": "int", "min": 0,
                                "max": (1 << FARE_BITS) - 1}}).encode(),
    )

    def frames():
        for shard in range(n_shards):
            cols = gen_shard(seed, shard)
            ref.add_shard(shard, *cols)
            yield from shard_frames(shard, *cols)

    return loader.stream_frames(base, INDEX, frames(), pipeline=4, timeout=300)


def run_reads(base: str, ref: Reference, names) -> int:
    """Issue each named read once and compare it with the reference."""
    for name in names:
        pql = next(q.pql for q in READS if q.name == name)
        t0 = time.monotonic()
        got = query(base, INDEX, pql)["results"][0]
        want = ref.expected(name)
        check(got == want, f"{name}: server {got!r} != reference {want!r}")
        emit("read", name=name, equal=True,
             seconds=round(time.monotonic() - t0, 3))
    return len(names)


def explain_table(base: str) -> None:
    """The router's candidate costs per query, plan only (evidence for
    ROADMAP Queue 1 item 3: what "auto" would pick at this size)."""
    table = {}
    for read in READS:
        plan = http_json(f"{base}/index/{INDEX}/query?explain=true",
                         read.pql.encode())["explain"]["calls"][0]
        costs = {p: c["estimatedSeconds"] for p, c in plan["candidates"].items()}
        table[read.name] = {
            "workWords": plan["estimatedWorkWords"],
            "estimatedSeconds": costs,
            "autoWouldPick": min(costs, key=costs.get),
        }
    emit("explain", table=table)


def check_routed(base: str, before: dict, reads: int, path: str) -> dict:
    after = routed(base)
    delta = {k: after[k] - before[k] for k in after}
    check(delta["host"] == 0, f"{delta['host']} reads ran on the host engine")
    if path == "mesh":
        check(delta["mesh"] > 0, "no read took the mesh route")
        check(delta["mesh"] + delta["device"] == reads,
              f"issued {reads} reads, routed {delta}")
    else:
        check(delta["device"] == reads,
              f"issued {reads} reads, device engine served {delta['device']}")
    emit("routed", reads_issued=reads, **delta)
    return after


def residency_facts(base: str, dense_bytes: int, mesh_devices: int | None):
    res = http_json(base + "/debug/resources")["subsystems"]["deviceResidency"]
    # the stack ledger counts what ONE device holds: its share of every
    # stack placed over several devices
    share = dense_bytes // max(1, res.get("devicesSpanned", 1))
    check(res["used"] >= share,
          f"resident {res['used']} B a device < its share {share} B of the "
          f"fields' dense size {dense_bytes} B")
    if mesh_devices is not None:
        check(res["devicesSpanned"] == mesh_devices,
              f"stacks span {res['devicesSpanned']} of {mesh_devices} devices")
        check(res["replicatedStacks"] == 0,
              f"{res['replicatedStacks']} stacks replicated, not partitioned")
    emit("residency", resident_bytes=res["used"], budget_bytes=res["limit"],
         dense_bytes_loaded=dense_bytes, stacks=res["stacks"],
         devices_spanned=res["devicesSpanned"],
         replicated_stacks=res["replicatedStacks"],
         device_memory=res["deviceMemory"])


def apply_writes(base: str, seed: int, n_shards: int, ref: Reference) -> None:
    """Set/Clear on resident rows, each acknowledged before the next."""
    first, last = gen_shard(seed, 0), gen_shard(seed, n_shards - 1)
    base_last = (n_shards - 1) * SHARD_WIDTH
    writes = [
        # a column that is not in cab_type row 3 joins it
        ("set", "cab_type", 3, int(np.flatnonzero(first[0] != 3)[0])),
        # a column leaves cab_type row 0
        ("clear", "cab_type", 0, base_last + int(np.flatnonzero(last[0] == 0)[0])),
        ("set", "passenger_count", 4,
         base_last + int(np.flatnonzero(last[1] != 4)[1])),
        # a new largest fare: rewrites several bit slices of one column
        ("set", "fare", (1 << FARE_BITS) - 1, int(np.flatnonzero(first[2] < 1000)[0])),
    ]
    for kind, field, value, col in writes:
        call = "Set" if kind == "set" else "Clear"
        out = query(base, INDEX, f"{call}({col}, {field}={value})")
        check(out["results"] == [True],
              f"{call}({col}, {field}={value}) not acknowledged: {out}")
        ref.apply_write(kind, field, value, col)
    emit("writes", acknowledged=len(writes))


def load_rare(base: str, seed: int, n_shards: int) -> dict[int, np.ndarray]:
    """The over-budget index: a few rows, ≤ 1 % dense, spread to row id
    2047 so the dense [R, S, W] projection cannot fit the stack budget.
    Returns row → sorted global column ids (the reference)."""
    rng = np.random.default_rng([seed, 0xA4E])
    n_cols = n_shards * SHARD_WIDTH
    rows: dict[int, np.ndarray] = {}
    for r in RARE_SPARSE_ROWS:
        rows[r] = np.unique(rng.integers(0, n_cols, 1500))
    span = min(8192, SHARD_WIDTH // 8)
    n_runs = min(64, n_cols // span // 4)
    for r in RARE_RUN_ROWS:
        starts = rng.choice(n_cols // span, n_runs, replace=False) * span
        rows[r] = np.sort((starts[:, None] + np.arange(span)[None]).ravel())
    # more bits than a sparse container holds, in more runs than a run
    # container holds: the chooser keeps the row as a dense plane
    rows[RARE_DENSE_ROW] = np.unique(
        rng.integers(0, n_cols, max(n_cols // 200, 4096))
    )
    http_json(f"{base}/index/{RARE_INDEX}", b"{}")
    http_json(f"{base}/index/{RARE_INDEX}/field/{RARE_FIELD}", b"{}")
    row_ids = np.concatenate(
        [np.full(c.size, r, dtype=np.uint64) for r, c in rows.items()]
    )
    col_ids = np.concatenate(list(rows.values())).astype(np.uint64)
    loader.stream_frames(
        base, RARE_INDEX,
        ((RARE_FIELD, "standard", s, f, n)
         for s, f, n in loader.build_frames(row_ids, col_ids)),
        timeout=300,
    )
    return rows


def run_residency(base: str, rows: dict[int, np.ndarray]) -> int:
    """Row algebra over the over-budget field, each query three times
    under ?profile=true (bypasses the result cache): the first touch
    serves a cold host-packed plane, the second promotes the row into
    its compressed container store, the third reads it resident."""
    s1, s2, s3 = RARE_SPARSE_ROWS
    r1, r2 = RARE_RUN_ROWS
    d = RARE_DENSE_ROW
    t = RARE_FIELD
    cases = [
        (f"Count(Row({t}={s1}))", rows[s1].size),
        (f"Count(Row({t}={r1}))", rows[r1].size),
        (f"Count(Intersect(Row({t}={s2}), Row({t}={r2})))",
         np.intersect1d(rows[s2], rows[r2]).size),
        (f"Count(Union(Row({t}={s3}), Row({t}={d}), Row({t}={r1})))",
         np.union1d(np.union1d(rows[s3], rows[d]), rows[r1]).size),
        (f"Count(Difference(Row({t}={d}), Row({t}={s1})))",
         np.setdiff1d(rows[d], rows[s1]).size),
    ]
    plan = http_json(f"{base}/index/{RARE_INDEX}/query?explain=true",
                     cases[0][0].encode())["explain"]["calls"][0]
    check(plan["residency"]["tiered"],
          f"{RARE_FIELD} fits the stack budget; nothing tiered to prove: {plan}")
    issued = 0
    for pql, want in cases:
        for _ in range(3):
            got = query(base, RARE_INDEX, pql, "?profile=true")["results"][0]
            check(got == int(want), f"{pql}: server {got} != reference {want}")
            issued += 1
    snap = http_json(base + "/debug/vars")["deviceResidency"]
    check(snap["rowsPromoted"] >= 6 and snap["coldUploads"] > 0,
          f"tiered residency counters did not move: {snap}")
    by_kind = snap["bytesByContainer"]
    check(all(by_kind[k] > 0 for k in ("sparse", "run", "dense")),
          f"a container store was never built: {by_kind}")
    emit("tiered", queries=issued, equal=True, rows_promoted=snap["rowsPromoted"],
         cold_uploads=snap["coldUploads"], resident_rows=snap["residentRows"],
         bytes_by_container=by_kind, budget_bytes=snap["budgetBytes"])
    return issued


def boot_line(which: str, srv: ServerProcess, cache_dir: str) -> None:
    """After a boot's first device answer."""
    emit("boot", which=which, seconds_to_listening=round(srv.listening_s, 2),
         seconds_to_first_device_answer=round(srv.since_spawn(), 2),
         compile_cache_entries=cache_entries(cache_dir),
         **srv.compile_counts())


def stop_and_count(which: str, srv: ServerProcess, cache_dir: str) -> int:
    """Stop the server; the boot's whole compile account → cache entries."""
    srv.stop()
    entries = cache_entries(cache_dir)
    emit("compiles", which=which, compile_cache_entries=entries,
         **srv.compile_counts())
    return entries


def run(*, shards: int, residency_shards: int, seed: int, mesh: bool,
        expect_platform: str, expect_count: int | None = None,
        stack_budget_bytes: int | None = None) -> dict:
    """Every phase, in order; raises on the first that fails. Returns the
    device facts for the last line. ``expect_platform`` is an argument
    and not an option: the command line always demands ``tpu``."""
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    route = "mesh" if mesh else "device"
    config_path = os.path.join(workdir, "server.toml")
    with open(config_path, "w") as f:
        f.write(f'route-mode = "{route}"\n')
        if stack_budget_bytes:
            f.write(f"device-stack-budget-bytes = {stack_budget_bytes}\n")
    srv = ServerProcess(workdir, "boot1", config_path,
                        os.path.join(workdir, "data"))
    try:
        srv.start()
        facts, diag = device_facts(srv.base, expect_platform, expect_count)
        cache_dir = diag["compile_cache_dir"]
        emit("start", device=facts, shards=shards,
             columns=shards * SHARD_WIDTH, shard_width=SHARD_WIDTH, seed=seed,
             route_mode=route, demo_shards=DEMO_SHARDS,
             cut=None if shards >= DEMO_SHARDS else SHARDS_CUT_REASON,
             native_kernels={"server": diag["native_kernels"],
                             "loader": native.available()},
             compile_cache_dir=cache_dir,
             compile_cache_entries=cache_entries(cache_dir))

        ref = Reference(seed)
        stats = load_taxi(srv.base, seed, shards, ref)
        # the server's own time inside the import route, summed over the
        # posts: far below pipeline × seconds means the builder here was
        # the slower half
        emit("load", index=INDEX, server_import_seconds=round(sum(
            metrics(srv.base, "import_batch_seconds_sum").values()), 1),
            **stats)

        before = routed(srv.base)
        reads = run_reads(srv.base, ref, [READS[0].name])
        boot_line("cold", srv, cache_dir)
        reads += run_reads(srv.base, ref, [q.name for q in READS[1:]])
        before = check_routed(srv.base, before, reads, route)
        explain_table(srv.base)
        # dense size of what was loaded: cab + passengers + bsi rows, one
        # [S, W] plane of SHARD_WIDTH / 8 bytes per shard each
        dense_rows = len(CAB_SHARES) + len(PASSENGER_SHARES) + FARE_BITS + 1
        residency_facts(srv.base, dense_rows * shards * SHARD_WIDTH // 8,
                        facts["count"] if mesh else None)
        if mesh:
            stop_and_count("cold", srv, cache_dir)
            return facts

        rare = load_rare(srv.base, seed, residency_shards)
        reads = run_residency(srv.base, rare)
        before = check_routed(srv.base, before, reads, route)

        apply_writes(srv.base, seed, shards, ref)
        reads = run_reads(srv.base, ref, REREADS)
        check_routed(srv.base, before, reads, route)
        deltas = http_json(srv.base + "/debug/vars")["stackCache"]["deltaUpdates"]
        check(deltas > 0, "the writes never took the delta scatter path")
        emit("delta", delta_updates=deltas)
        entries_cold = stop_and_count("cold", srv, cache_dir)

        srv = ServerProcess(workdir, "boot2", config_path, srv.data_dir)
        srv.start()
        device_facts(srv.base, expect_platform, expect_count)
        before = routed(srv.base)
        reads = run_reads(srv.base, ref, REREADS[:1])
        boot_line("warm", srv, cache_dir)
        reads += run_reads(srv.base, ref, REREADS[1:])
        check_routed(srv.base, before, reads, route)
        entries_warm = stop_and_count("warm", srv, cache_dir)
        check(entries_warm == entries_cold,
              f"the restart added compile cache entries: {entries_cold} → "
              f"{entries_warm}")
        return facts
    except BaseException:
        # the failing phase's own message follows; the server's last
        # words usually say why
        print(f"--- {srv.log_path} (tail)\n{srv.log_tail(60)}---",
              file=sys.stderr, flush=True)
        raise
    finally:
        srv.kill()
        shutil.rmtree(workdir, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=DEFAULT_SEED)
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: one server, mesh route, reads only")
    args = ap.parse_args()
    facts = run(shards=SHARDS, residency_shards=RESIDENCY_SHARDS,
                seed=args.seed, mesh=args.mesh, expect_platform="tpu",
                expect_count=4 if args.mesh else None)
    print(json.dumps({"ok": True, "device": facts}), flush=True)


if __name__ == "__main__":
    main()
