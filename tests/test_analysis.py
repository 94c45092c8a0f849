"""Tier-1 gate for the static-analysis suite (tools/analysis).

Enforces the two acceptance invariants:

- the SHIPPED tree is clean: ``python -m tools.analysis pilosa_tpu``
  exits 0 — a PR that introduces a violation fails here;
- the suite actually detects what it claims: every seeded-violation
  fixture exits non-zero naming its rule, every clean twin exits 0, and
  mutating the live tree (removing a hostpath call type, dropping a
  route handler, adding an undocumented config knob) flips the analyzer
  to failing.

Plus unit tests for the two autofixes, including idempotence.
"""

from __future__ import annotations

import contextlib
import io
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "analysis"

if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.analysis.engine import Project, run as run_rules  # noqa: E402
from tools.analysis.fixes import fix_monotonic, fix_with_locks  # noqa: E402


def run_analyzer(*args: str) -> tuple[int, str]:
    from tools.analysis.__main__ import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(buf):
        rc = main(list(args))
    return rc, buf.getvalue()


# ------------------------------------------------------------- live tree
def test_live_tree_is_clean():
    rc, out = run_analyzer(str(REPO / "pilosa_tpu"))
    assert rc == 0, f"analyzer must pass on the shipped tree:\n{out}"


def test_module_entrypoint_runs():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "pilosa_tpu"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_rule_registry_complete():
    rc, out = run_analyzer("--list-rules")
    assert rc == 0
    for name in (
        "readback",
        "raw-acquire",
        "lock-order",
        "parity",
        "observability",
        "config-drift",
        "bare-except",
        "broad-except",
        "mutable-default",
        "wall-clock",
        "resilience",
        "asyncpurity",
        "durability",
        "cacheinvariant",
        "loop-purity",
    ):
        assert name in out, f"rule {name} missing from registry"


# ---------------------------------------------------------- rule fixtures
@pytest.mark.parametrize(
    "fixture, rules",
    [
        ("readback_bad.py", ["readback"]),
        ("locks_bad.py", ["raw-acquire", "lock-order"]),
        (
            "banned_bad.py",
            ["bare-except", "broad-except", "mutable-default", "wall-clock"],
        ),
        ("resilience_bad.py", ["resilience"]),
        ("asyncpurity_bad.py", ["asyncpurity"]),
        # lives under core/ so the holder-data-layer scope applies
        ("core/durability_bad.py", ["durability"]),
        # transitive fixtures: the violation hides ≥1 call frame below
        # the entry point — only the call-graph walk can reach it
        ("asyncpurity_transitive_bad.py", ["asyncpurity"]),
        ("readback_transitive_bad.py", ["readback"]),
        ("lockorder_deep_bad.py", ["lock-order"]),
    ],
)
def test_seeded_fixture_fails(fixture, rules):
    rc, out = run_analyzer(str(FIXTURES / fixture))
    assert rc != 0, f"{fixture} must fail the analyzer"
    for r in rules:
        assert f"[{r}]" in out, f"{fixture} must trip rule {r}:\n{out}"


@pytest.mark.parametrize(
    "fixture",
    [
        "readback_ok.py",
        "locks_ok.py",
        "banned_ok.py",
        "resilience_ok.py",
        "asyncpurity_ok.py",
        "core/durability_ok.py",
        "asyncpurity_transitive_ok.py",
        "readback_transitive_ok.py",
        "lockorder_deep_ok.py",
    ],
)
def test_clean_fixture_passes(fixture):
    rc, out = run_analyzer(str(FIXTURES / fixture))
    assert rc == 0, f"{fixture} must pass:\n{out}"


def test_pragma_suppresses(tmp_path):
    # readback_ok.py contains a genuine sync carrying the pragma: with
    # the pragma the file passes, with it stripped the same file fails —
    # both halves, or the test can't tell suppression from a dead rule
    src = (FIXTURES / "readback_ok.py").read_text()
    assert "# pilosa: allow(readback)" in src
    rc, _ = run_analyzer(str(FIXTURES / "readback_ok.py"))
    assert rc == 0
    stripped = tmp_path / "readback_stripped.py"
    stripped.write_text(src.replace("# pilosa: allow(readback)", ""))
    rc, out = run_analyzer(str(stripped), "--rule", "readback")
    assert rc != 0, "stripping the pragma must surface the violation"
    assert "[readback]" in out


# ------------------------------------------------------ mutated live tree
@pytest.fixture
def tree_copy(tmp_path):
    dst = tmp_path / "repo"
    (dst / "docs").parent.mkdir(parents=True, exist_ok=True)
    shutil.copytree(
        REPO / "pilosa_tpu",
        dst / "pilosa_tpu",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copytree(REPO / "docs", dst / "docs")
    return dst


def mutate(path: Path, old: str, new: str) -> None:
    text = path.read_text()
    assert old in text, f"mutation anchor missing from {path}: {old!r}"
    path.write_text(text.replace(old, new))


def check_tree(root: Path) -> tuple[int, str]:
    return run_analyzer(str(root / "pilosa_tpu"), "--root", str(root))


def test_tree_copy_baseline_clean(tree_copy):
    rc, out = check_tree(tree_copy)
    assert rc == 0, out


def test_parity_missing_host_method_fails(tree_copy):
    # remove a whole hostpath call type: the exact scenario the rule
    # exists for — the router would 500 any TopN it sends host-side
    mutate(
        tree_copy / "pilosa_tpu" / "executor" / "hostpath.py",
        "def topn_pairs(",
        "def topn_pairs_removed(",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[parity]" in out and "topn_pairs" in out


def test_parity_missing_planner_branch_fails(tree_copy):
    mutate(
        tree_copy / "pilosa_tpu" / "executor" / "hostpath.py",
        'if name == "Shift":',
        'if name == "ShiftDisabled":',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[parity]" in out and "'Shift'" in out


def test_parity_mesh_program_removed_fails(tree_copy):
    # drop a bitmap call from the mesh read surface WITHOUT a fallback
    # annotation: the router's mesh path would mis-handle that call type
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "mesh.py",
        '"Xor",',
        "",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[parity]" in out and "'Xor'" in out and "MESH_PROGRAMS" in out


def test_parity_mesh_builder_removed_fails(tree_copy):
    # a missing program builder is a runtime AttributeError on whichever
    # call family the router sends mesh-side
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "mesh.py",
        "def minmax_tree(",
        "def minmax_tree_removed(",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[parity]" in out and "minmax_tree" in out


def test_parity_mesh_fallback_annotation_suffices(tree_copy):
    # moving a call from MESH_PROGRAMS to the fallback annotation set is
    # an ALLOWED state (explicit, reviewed fallback — not drift)
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "mesh.py",
        'MESH_FALLBACK_CALLS = {"Shift"}',
        'MESH_FALLBACK_CALLS = {"Shift", "Xor"}',
    )
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "mesh.py",
        '    "Xor",\n',
        "",
    )
    rc, out = check_tree(tree_copy)
    assert rc == 0, out


def test_parity_container_decode_branch_removed_fails(tree_copy):
    # drop the host equivalence branch for the "run" container kind:
    # tiered rows the chooser packs as runs would have no host-side
    # decode — the exact drift the container-parity rule exists for
    mutate(
        tree_copy / "pilosa_tpu" / "executor" / "hostpath.py",
        'elif kind == "run":',
        'elif kind == "run-disabled":',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[parity]" in out and "'run'" in out and "decode_container" in out


def test_parity_container_kind_added_without_decode_fails(tree_copy):
    # grow the chooser taxonomy without teaching either engine: both
    # the host and the device decode surfaces must flag the new kind
    mutate(
        tree_copy / "pilosa_tpu" / "executor" / "residency.py",
        'CONTAINER_KINDS = {"dense", "sparse", "run"}',
        'CONTAINER_KINDS = {"dense", "sparse", "run", "bitpacked"}',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[parity]" in out and "'bitpacked'" in out


def test_parity_device_tiered_leaf_branch_removed_fails(tree_copy):
    mutate(
        tree_copy / "pilosa_tpu" / "executor" / "compile.py",
        'elif kind == "sparse":\n\n            def run',
        'elif kind == "sparse-disabled":\n\n            def run',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[parity]" in out and "_tiered_leaf" in out


def test_observability_missing_handler_fails(tree_copy):
    mutate(
        tree_copy / "pilosa_tpu" / "server" / "http.py",
        "def h_version(",
        "def x_version(",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[observability]" in out and "version" in out


def test_observability_untimed_fanout_fails(tree_copy):
    # strip every timing call: the one function that wraps
    # client.query_node (_timed_query_node) loses its histogram and the
    # per-leg latency contract goes dark
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "cluster.py",
        "stats.timing(",
        "stats.notiming_(",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[observability]" in out and "query_node" in out


def test_config_drift_undocumented_field_fails(tree_copy):
    mutate(
        tree_copy / "pilosa_tpu" / "utils" / "config.py",
        'bind: str = "127.0.0.1:10101"',
        'bind: str = "127.0.0.1:10101"\n    brand_new_knob: int = 7',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[config-drift]" in out and "brand_new_knob" in out


def test_config_drift_undocumented_env_fails(tree_copy):
    mutate(
        tree_copy / "pilosa_tpu" / "executor" / "router.py",
        '"PILOSA_TPU_ROUTE_MODE"',
        '"PILOSA_TPU_SECRET_KNOB"',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[config-drift]" in out and "PILOSA_TPU_SECRET_KNOB" in out


def test_config_drift_stale_doc_key_fails(tree_copy):
    mutate(
        tree_copy / "docs" / "configuration.md",
        "| `bind` |",
        "| `bind-retired` |",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[config-drift]" in out and "bind-retired" in out


def test_readback_leak_in_scheduler_fails(tree_copy):
    # the scheduler is NOT blanket-sanctioned like the rest of
    # executor/: a sync anywhere outside the named settlement function
    # (fetch_wave) must flag — coordinating many requests' results is
    # exactly where an accidental early sync would serialize every wave
    mutate(
        tree_copy / "pilosa_tpu" / "executor" / "scheduler.py",
        "    def snapshot(self) -> dict:",
        "    def snapshot(self) -> dict:\n"
        "        probe = jnp.zeros(8)\n"
        "        _leak = float(np.asarray(probe).sum())\n",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[readback]" in out and "scheduler.py" in out


def test_readback_settlement_layer_stays_sanctioned(tree_copy):
    # renaming fetch_wave strips its explicit sanction: the transfer
    # inside it must then flag (proves the sanction is the NAME, not
    # the file)
    mutate(
        tree_copy / "pilosa_tpu" / "executor" / "scheduler.py",
        "def fetch_wave(",
        "def fetch_wave_renamed(",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[readback]" in out and "scheduler.py" in out


def test_observability_missing_batch_handler_fails(tree_copy):
    # the multi-query /internal route: client half spoken, server half
    # gone — the rule must notice before a 404 does
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "cluster.py",
        "def _h_query_batch(",
        "def _x_query_batch(",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[observability]" in out and "_h_query_batch" in out


def test_observability_unspanned_batch_handler_fails(tree_copy):
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "cluster.py",
        'with GLOBAL_TRACER.span("cluster.query_batch", queries=len(entries)):',
        "if True:",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[observability]" in out and "_h_query_batch" in out


def test_parity_scheduler_bypassing_dispatch_fails(tree_copy):
    # the batch enqueue path must go through Executor.dispatch (the
    # parity-covered entry); renaming the call simulates a rewrite that
    # grows its own dispatch
    mutate(
        tree_copy / "pilosa_tpu" / "executor" / "scheduler.py",
        "executor.dispatch(",
        "executor.dispatch_private(",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[parity]" in out and "dispatch" in out


def test_parity_scheduler_call_name_switch_fails(tree_copy):
    # a call.name-compare in the scheduler = a third dispatch table the
    # executor/hostpath parity diff cannot see
    mutate(
        tree_copy / "pilosa_tpu" / "executor" / "scheduler.py",
        '        if self.mode == "off":',
        '        name = calls[0].name\n'
        '        if name == "TopN":\n'
        "            pass\n"
        '        if self.mode == "off":',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[parity]" in out and "TopN" in out


def test_readback_leak_in_server_fails(tree_copy):
    mutate(
        tree_copy / "pilosa_tpu" / "server" / "diagnostics.py",
        "    def snapshot(self) -> dict:",
        "    def snapshot(self) -> dict:\n"
        "        import jax.numpy as jnp\n"
        "        import numpy as np\n"
        "        probe = jnp.zeros(8)\n"
        "        _leak = float(np.asarray(probe).sum())\n",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[readback]" in out


def test_resilience_naked_transport_fails(tree_copy):
    # the cluster constructing the raw transport directly: retries,
    # breakers, deadlines and fault injection all silently vanish from
    # the whole distributed read path
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "cluster.py",
        "self.client = make_resilient_client(",
        "self.client = InternalClient(",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[resilience]" in out and "InternalClient" in out


def test_resilience_write_in_retry_scope_fails(tree_copy):
    # a write RPC migrating into the retry set = duplicated writes on
    # transient failures; the rule reads the literal sets structurally
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "resilience.py",
        '        "query_node",\n        "query_batch_node",',
        '        "query_node",\n        "import_node",\n'
        '        "query_batch_node",',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[resilience]" in out and "import_node" in out


def test_resilience_unflagged_write_leg_fails(tree_copy):
    # the write router dropping write=True would put Set/Clear legs on
    # the retried, coalesced read RPC
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "cluster.py",
        "write=True,",
        "write=False,",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[resilience]" in out and "write=True" in out


def test_durability_bare_oplog_append_fails(tree_copy):
    # regress the ops-log append to a bare open(): the write leaves the
    # WAL fsync policy AND the FS fault hook — acknowledged bits could
    # die in the page cache and the chaos suite would never know
    mutate(
        tree_copy / "pilosa_tpu" / "core" / "fragment.py",
        "durable.append_wal(self.path, framed)",
        'open(self.path, "ab").write(framed)',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[durability]" in out and "bare write-mode open" in out


def test_durability_rename_without_dirfsync_fails(tree_copy):
    # drop the parent-dir fsync from the sanctioned rename: every
    # atomic write in the tree silently loses its crash guarantee
    mutate(
        tree_copy / "pilosa_tpu" / "utils" / "durable.py",
        "fsync_dir(os.path.dirname(os.path.abspath(dst)))",
        "os.path.dirname(os.path.abspath(dst))",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[durability]" in out and "replace_durable" in out


def test_asyncpurity_sleep_in_coroutine_fails(tree_copy):
    # a time.sleep smuggled into the event loop's connection coroutine:
    # every connection the process serves would stall behind it — the
    # exact failure mode the event-driven front end replaced
    # thread-per-request to avoid (docs/serving.md)
    mutate(
        tree_copy / "pilosa_tpu" / "server" / "eventloop.py",
        "head = await self._read_head(reader, conn)\n",
        "time.sleep(0)\n                head = await self._read_head(reader, conn)\n",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[asyncpurity]" in out and "time.sleep" in out


def test_asyncpurity_thread_spawn_in_coroutine_fails(tree_copy):
    # per-request thread spawns from the loop would silently rebuild the
    # thread-per-request model the bounded worker pool replaced
    mutate(
        tree_copy / "pilosa_tpu" / "server" / "eventloop.py",
        "work = self._pool.submit(\n"
        "                self._run_request, raw, writer, deadline,\n"
        "                direct_ok, wait_s, arrival,\n"
        "            )",
        "_t = threading.Thread(\n"
        "                target=self._run_request, args=(raw, writer, deadline)\n"
        "            )\n"
        "            _t.start()\n"
        "            work = concurrent.futures.Future()",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[asyncpurity]" in out and "threading.Thread" in out


# ----------------------------------------------------------------- fixes
def _violations_of(path: Path, text: str, rules: list[str]) -> list:
    tmp = path.parent / ("fixed_" + path.name)
    tmp.write_text(text)
    try:
        project = Project.discover(tmp.parent, [tmp])
        return [v for v in run_rules(project, only=rules)]
    finally:
        tmp.unlink()


def test_fix_with_locks_removes_violation_and_is_idempotent(tmp_path):
    src = (FIXTURES / "locks_bad.py").read_text()
    fixed = fix_with_locks(src)
    assert fixed != src
    assert ".acquire()" not in fixed
    compile(fixed, "<fixed>", "exec")  # still valid python
    p = tmp_path / "locks_case.py"
    vs = _violations_of(p, fixed, ["raw-acquire"])
    assert vs == [], f"raw-acquire must be fixed: {[v.format() for v in vs]}"
    assert fix_with_locks(fixed) == fixed, "second run must be a no-op"


def test_fix_monotonic_removes_violation_and_is_idempotent(tmp_path):
    src = (FIXTURES / "banned_bad.py").read_text()
    fixed = fix_monotonic(src)
    assert fixed != src
    compile(fixed, "<fixed>", "exec")
    # BOTH the duration arithmetic and the feeding assignment move to
    # the monotonic clock — fixing only one side would be a worse bug
    assert "time.monotonic() - t0" in fixed
    assert "t0 = time.monotonic()" in fixed
    p = tmp_path / "clock_case.py"
    vs = _violations_of(p, fixed, ["wall-clock"])
    assert vs == []
    assert fix_monotonic(fixed) == fixed, "second run must be a no-op"


def test_fix_respects_wall_clock_pragmas():
    # the two intentionally wall-clock sites (the persisted tombstone
    # TTL, the trace epoch anchor) carry pragmas — --fix must not
    # rewrite them
    from tools.analysis.fixes import apply_fixes

    for rel in (
        "pilosa_tpu/core/attrstore.py",
        "pilosa_tpu/utils/tracing.py",
    ):
        src = (REPO / rel).read_text()
        assert apply_fixes(src) == src, f"--fix must not touch {rel}"


def test_fix_monotonic_feed_keys_are_function_scoped():
    src = (
        "import time\n\n\n"
        "def measure():\n"
        "    t0 = time.time()\n"
        "    return time.time() - t0\n\n\n"
        "def stamp():\n"
        "    t0 = time.time()  # a persisted wall timestamp, same name\n"
        "    return {'ts': t0}\n"
    )
    fixed = fix_monotonic(src)
    assert "return time.monotonic() - t0" in fixed
    assert fixed.count("t0 = time.monotonic()") == 1, fixed
    assert "t0 = time.time()  # a persisted wall timestamp" in fixed


def test_empty_target_is_usage_error(tmp_path):
    empty = tmp_path / "nothing_here"
    empty.mkdir()
    rc, out = run_analyzer(str(empty))
    assert rc == 2, f"zero files must not pass the gate: rc={rc}\n{out}"
    assert "no python files" in out


def test_raw_acquire_wrong_receiver_release(tmp_path):
    p = tmp_path / "wrong_release.py"
    p.write_text(
        "import threading\n"
        "lock_a = threading.Lock()\n"
        "lock_b = threading.Lock()\n\n\n"
        "def leak():\n"
        "    lock_a.acquire()\n"
        "    try:\n"
        "        pass\n"
        "    finally:\n"
        "        lock_b.release()  # releases the WRONG lock\n"
    )
    rc, out = run_analyzer(str(p), "--rule", "raw-acquire")
    assert rc != 0, "a finally releasing a different lock must not guard"
    assert "[raw-acquire]" in out


def test_fix_with_locks_nested_pairs(tmp_path):
    # nested raw pairs in one block, plus an unrelated release after —
    # the fixer must produce properly nested with-blocks and must not
    # touch the unrelated line (regression: stale line numbers after
    # the inner rewrite's deletion once corrupted exactly this shape)
    src = (
        "import threading\n"
        "a_lock = threading.Lock()\n"
        "b_lock = threading.Lock()\n"
        "c_lock = threading.Lock()\n\n\n"
        "def nested():\n"
        "    a_lock.acquire()\n"
        "    b_lock.acquire()\n"
        "    work()\n"
        "    b_lock.release()\n"
        "    a_lock.release()\n"
        "    c_lock.release()\n\n\n"
        "def work():\n"
        "    pass\n"
    )
    fixed = fix_with_locks(src)
    compile(fixed, "<fixed>", "exec")
    assert "with a_lock:" in fixed and "with b_lock:" in fixed
    assert ".acquire()" not in fixed
    assert "a_lock.release()" not in fixed and "b_lock.release()" not in fixed
    assert "c_lock.release()" in fixed, "unrelated release must survive"
    p = tmp_path / "nested_case.py"
    vs = _violations_of(p, fixed, ["raw-acquire"])
    assert vs == [], [v.format() for v in vs]
    assert fix_with_locks(fixed) == fixed


def test_fix_with_locks_skips_early_release_in_nested_block():
    # an early release inside an if-block between the pair breaks the
    # simple pattern: rewriting would double-release (RuntimeError) on
    # the early path — the fixer must leave it alone (rule keeps firing)
    src = (
        "import threading\n"
        "lock = threading.Lock()\n\n\n"
        "def tricky(err):\n"
        "    lock.acquire()\n"
        "    if err:\n"
        "        lock.release()\n"
        "        return None\n"
        "    work()\n"
        "    lock.release()\n"
        "    return True\n\n\n"
        "def work():\n"
        "    pass\n"
    )
    assert fix_with_locks(src) == src


def test_fix_monotonic_module_scope_skips_function_locals():
    # a module-level duration must not drag a same-named assignment in
    # an unrelated function onto the monotonic clock
    src = (
        "import time\n\n"
        "t0 = time.time()\n"
        "elapsed = time.time() - t0\n\n\n"
        "def stamp():\n"
        "    t0 = time.time()  # persisted wall timestamp\n"
        "    return {'ts': t0}\n"
    )
    fixed = fix_monotonic(src)
    assert "elapsed = time.monotonic() - t0" in fixed
    assert fixed.splitlines()[2] == "t0 = time.monotonic()"
    assert "    t0 = time.time()  # persisted wall timestamp" in fixed


def test_fix_with_locks_skips_multiline_strings():
    # reindenting body lines would rewrite a triple-quoted constant's
    # VALUE — such blocks must be left alone (the rule keeps firing)
    src = (
        "import threading\n"
        "lock = threading.Lock()\n\n\n"
        "def docy():\n"
        "    lock.acquire()\n"
        '    doc = """a\n'
        'b"""\n'
        "    lock.release()\n"
        "    return doc\n"
    )
    assert fix_with_locks(src) == src


def test_fix_cli_flag(tmp_path):
    target = tmp_path / "locks_cli.py"
    target.write_text((FIXTURES / "locks_bad.py").read_text())
    rc, _ = run_analyzer(str(target), "--rule", "raw-acquire")
    assert rc != 0
    rc, out = run_analyzer(str(target), "--rule", "raw-acquire", "--fix")
    assert rc == 0, out
    # rerunning --fix on the fixed file changes nothing
    before = target.read_text()
    rc, _ = run_analyzer(str(target), "--rule", "raw-acquire", "--fix")
    assert rc == 0
    assert target.read_text() == before


# ------------------------------------------------- metric⇄docs drift
def test_obsmetrics_fixture_ok():
    root = FIXTURES / "obsmetrics_ok"
    rc, out = run_analyzer(str(root / "pkg"), "--root", str(root))
    assert rc == 0, out


def test_obsmetrics_fixture_bad():
    root = FIXTURES / "obsmetrics_bad"
    rc, out = run_analyzer(str(root / "pkg"), "--root", str(root))
    assert rc != 0
    # undocumented registration AND stale catalog row both fire
    assert "[observability]" in out
    assert "dark_metric" in out
    assert "ghost_metric" in out


def test_metric_drift_dropped_doc_row_fails(tree_copy):
    # drop one catalog row from the live docs: the registered metric
    # behind it goes undocumented and the tree must go red
    mutate(
        tree_copy / "docs" / "observability.md",
        "| `pilosa_tpu_queries_routed` |",
        "| `retired_queries_routed` |",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[observability]" in out and "queries_routed" in out


def test_metric_drift_undocumented_registration_fails(tree_copy):
    # register a brand-new metric with no catalog row
    mutate(
        tree_copy / "pilosa_tpu" / "server" / "http.py",
        'self.stats.count("http_requests", tags={"route": name})',
        'self.stats.count("http_requests", tags={"route": name})\n'
        '                    self.stats.count("covert_channel_total")',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[observability]" in out and "covert_channel_total" in out


def test_metric_drift_covers_workload_families(tree_copy):
    # ISSUE 11: the metric⇄docs check must cover the slo_*/workload_*
    # families — dropping the slo_burn_rate catalog row leaves the
    # registered gauge undocumented and the tree must go red
    mutate(
        tree_copy / "docs" / "observability.md",
        "| `pilosa_tpu_slo_burn_rate` |",
        "| `retired_slo_burn_rate` |",
    )
    mutate(
        tree_copy / "docs" / "observability.md",
        "| `pilosa_tpu_workload_observed_total` |",
        "| `retired_workload_observed_total` |",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "slo_burn_rate" in out
    assert "workload_observed_total" in out


def test_cacheinvariant_fixture_ok():
    root = FIXTURES / "cacheinvariant_ok"
    rc, out = run_analyzer(str(root / "server"), "--root", str(root))
    assert rc == 0, out


def test_cacheinvariant_fixture_bad():
    root = FIXTURES / "cacheinvariant_bad"
    rc, out = run_analyzer(str(root / "server"), "--root", str(root))
    assert rc != 0
    assert "[cacheinvariant]" in out
    assert "import_bits" in out and "delete_field" in out


def test_cacheinvariant_dropped_api_hook_fails(tree_copy):
    # strip the hook call from every API write path: each import/DDL
    # method now acks without retiring cached results — the exact
    # stale-serve the rule exists to prevent
    mutate(
        tree_copy / "pilosa_tpu" / "server" / "api.py",
        "self._invalidate_results(",
        "self._invalidate_nothing(",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[cacheinvariant]" in out
    assert "import_roaring" in out and "apply_schema" in out


def test_cacheinvariant_dropped_cluster_attr_hook_fails(tree_copy):
    # the replica-side attr-set receiver is stamp-blind: dropping its
    # hook leaves NO mechanism retiring that replica's cached results
    mutate(
        tree_copy / "pilosa_tpu" / "parallel" / "cluster.py",
        'self.server.api._invalidate_results(payload["index"])',
        'self.server.api._note_attr_write(payload["index"])',
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[cacheinvariant]" in out and "_apply_attr_write" in out


def test_cacheinvariant_noop_hook_fails(tree_copy):
    # a hook that stops reaching cache.invalidate() greens every write
    # path while retiring nothing — the rule must see through it
    mutate(
        tree_copy / "pilosa_tpu" / "server" / "api.py",
        "cache.invalidate(index)",
        "cache.touch(index)",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[cacheinvariant]" in out and "no-op" in out


# ------------------------------------------- call-graph transitive rules
def test_asyncpurity_transitive_attributes_the_root():
    # the violation anchors at the coroutine's call edge and names the
    # chain — the terminal sleep is one frame down
    rc, out = run_analyzer(
        str(FIXTURES / "asyncpurity_transitive_bad.py"), "--rule", "asyncpurity"
    )
    assert rc != 0
    assert "transitively reaches blocking call time.sleep()" in out
    assert "via _drain()" in out


def test_readback_transitive_attributes_the_call_edge():
    rc, out = run_analyzer(
        str(FIXTURES / "readback_transitive_bad.py"), "--rule", "readback"
    )
    assert rc != 0
    assert "transitively forces a device sync" in out
    assert "snapshot() calls _total()" in out


def test_looppurity_fixture_bad():
    root = FIXTURES / "looppurity_bad"
    rc, out = run_analyzer(
        str(root), "--root", str(root), "--rule", "loop-purity"
    )
    assert rc != 0
    # all three finding kinds fire: parser entry, blocking call, lock
    assert "reaches the parser" in out
    assert "blocking call time.sleep()" in out
    assert "acquired on the event-loop thread" in out


def test_looppurity_fixture_ok():
    # the clean twin passes EVERY rule: the loop-safe lock carries a
    # site pragma, the parse hides behind a pragma'd hand-off edge
    root = FIXTURES / "looppurity_ok"
    rc, out = run_analyzer(str(root), "--root", str(root))
    assert rc == 0, out


def test_looppurity_edge_pragma_is_load_bearing(tmp_path):
    # strip the edge escape from the clean twin: the walk descends into
    # _dispatch and the parser entry must surface
    root = tmp_path / "looppurity_stripped"
    shutil.copytree(FIXTURES / "looppurity_ok", root)
    f = root / "server" / "eventloop.py"
    f.write_text(f.read_text().replace("  # pilosa: allow(loop-purity)\n", "\n", 1))
    rc, out = run_analyzer(
        str(root), "--root", str(root), "--rule", "loop-purity"
    )
    assert rc != 0, "stripping the edge pragma must surface the parser entry"
    assert "reaches the parser" in out


def test_live_tree_mark_loop_thread_wired():
    # the loop-purity rule's runtime counterpart only works if the loop
    # thread actually marks itself
    src = (REPO / "pilosa_tpu" / "server" / "eventloop.py").read_text()
    assert "sanitize.mark_loop_thread()" in src


# --------------------------------------------------- cache + prune CLI
def test_prune_pragmas_reports_stale(tmp_path):
    p = tmp_path / "stale.py"
    p.write_text("import time\n\nX = 1  # pilosa: allow(wall-clock)\n")
    rc, out = run_analyzer(str(p), "--prune-pragmas")
    assert rc != 0
    assert "stale pragma allow(wall-clock)" in out


def test_prune_pragmas_live_tree_all_live():
    rc, out = run_analyzer(str(REPO / "pilosa_tpu"), "--prune-pragmas")
    assert rc == 0, out
    assert "pragmas: all live" in out


def test_prune_pragmas_rejects_rule_scoping():
    rc, _out = run_analyzer(
        str(FIXTURES / "readback_ok.py"), "--prune-pragmas", "--rule", "readback"
    )
    assert rc == 2, "staleness is only provable against the full rule set"


def test_ast_cache_written_and_invalidated(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("def f():\n    return 1\n")
    rc, _ = run_analyzer(str(p), "--root", str(tmp_path))
    assert rc == 0
    assert (tmp_path / ".analysis-ast-cache.pkl").exists()
    # a changed file must re-parse (mtime/size key), not serve the
    # stale tree — the rewritten file seeds an asyncpurity violation
    p.write_text("import time\n\n\nasync def f():\n    time.sleep(1)\n")
    rc, out = run_analyzer(
        str(p), "--root", str(tmp_path), "--rule", "asyncpurity"
    )
    assert rc != 0
    assert "[asyncpurity]" in out


def test_ast_cache_hit_reported_verbose(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("def f():\n    return 1\n")
    run_analyzer(str(p), "--root", str(tmp_path))
    rc, out = run_analyzer(str(p), "--root", str(tmp_path), "--verbose")
    assert rc == 0
    assert "1/1 ASTs from cache" in out
    assert "-- rule " in out, "per-rule timings must print under --verbose"


def test_emit_lock_graph_shape():
    rc, out = run_analyzer(
        str(FIXTURES / "lockorder_deep_bad.py"), "--emit-lock-graph"
    )
    assert rc == 0
    graph = json.loads(out)
    edges = {(a, b) for a, b, _src in graph["edges"]}
    assert ("Coordinator._plan_lock", "Coordinator._stats_lock") in edges
    assert ("Coordinator._stats_lock", "Coordinator._plan_lock") in edges
    assert "Coordinator._plan_lock" in graph["locks"]


def test_lock_graph_sees_through_constructors():
    # the first `make sanitize` run observed
    # Holder._create_lock -> TranslateStore._lock with NO static
    # explanation: the edge runs through Index()'s constructor
    # (`Index.__init__` opens `self.column_keys`, a ctor-typed attr).
    # Constructor + attr-type resolution closed that blind spot — this
    # pins it closed on the live tree.
    rc, out = run_analyzer(str(REPO / "pilosa_tpu"), "--emit-lock-graph")
    assert rc == 0
    edges = {(a, b) for a, b, _src in json.loads(out)["edges"]}
    assert ("Holder._create_lock", "TranslateStore._lock") in edges


def test_metric_drift_stale_doc_row_fails(tree_copy):
    # a catalog row whose metric no longer exists anywhere in code
    mutate(
        tree_copy / "docs" / "observability.md",
        "| `pilosa_tpu_queries_deduped` | counter | — |",
        "| `pilosa_tpu_queries_deduped` | counter | — |\n"
        "| `pilosa_tpu_vanished_metric` | counter | — | gone |",
    )
    rc, out = check_tree(tree_copy)
    assert rc != 0
    assert "[observability]" in out and "vanished_metric" in out
