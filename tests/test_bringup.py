"""Bring-up invariants (ISSUE 21): nothing hides the chip.

- importing the package initializes no JAX backend (a parent that has
  touched JAX holds the chip, and jax.distributed.initialize refuses to
  run after a backend exists);
- /info says what the process really runs on;
- the compile cache is placed from outside, or at one fixed path;
- an accelerator that cannot report its memory is an error, not 2 GiB;
- chip_smoke.py, rehearsed end to end on the CPU at a tiny size, and
  refusing to pass there when run as the command.
"""

import json
import os
import subprocess
import sys
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fresh_python(code: str, **env) -> str:
    """Run ``code`` in a fresh interpreter at the repo root → stdout."""
    full_env = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
    full_env.update(env)
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=full_env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip()


@pytest.mark.parametrize(
    "module,may_import_jax",
    [
        ("pilosa_tpu.server", True),
        ("pilosa_tpu.cli", True),
        ("pilosa_tpu.parallel.multihost", True),
        ("pilosa_tpu.executor", True),
        # chip_smoke.py's own imports: its process must never load jax
        ("pilosa_tpu.loader", False),
        ("pilosa_tpu.roaring", False),
        ("pilosa_tpu.shardwidth", False),
    ],
)
def test_import_touches_no_backend(module, may_import_jax):
    out = fresh_python(
        f"import sys, {module}\n"
        "bridge = sys.modules.get('jax._src.xla_bridge')\n"
        "print('jax' in sys.modules, "
        "sorted(bridge._backends) if bridge else [])"
    )
    imported, backends = out.split(" ", 1)
    assert backends == "[]", f"importing {module} initialized {backends}"
    assert may_import_jax or imported == "False", f"{module} imports jax"


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR set → the code sets no directory; unset
    → the one fixed path inside the checkout."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if from_env else {}
    out = fresh_python(
        "import jax, pilosa_tpu.ops\n"
        "print(jax.config.jax_compilation_cache_dir)",
        **env,
    )
    assert out == (str(tmp_path) if from_env else os.path.join(REPO, ".jax_cache"))


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = platform, "fake", stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize(
    "platform,stats,expect",
    [
        ("cpu", None, 2 << 30),
        ("tpu", {"bytes_limit": 16 * 10**9}, int(16 * 10**9 * 0.7)),
        ("tpu", None, RuntimeError),
        ("tpu", {"bytes_limit": 0}, RuntimeError),
    ],
)
def test_stack_budget_by_platform(monkeypatch, platform, stats, expect):
    """70 % of what the device reports; the CPU backend reports nothing
    and gets 2 GiB; an accelerator that reports nothing is an error."""
    import jax

    from pilosa_tpu.executor import compile as C

    monkeypatch.setattr(C, "_budget_cache", [])
    monkeypatch.setattr(C, "_budget_override", [])
    monkeypatch.delenv("PILOSA_TPU_STACK_BUDGET", raising=False)
    monkeypatch.setattr(
        jax, "local_devices", lambda: [_FakeDevice(platform, stats)]
    )
    if expect is RuntimeError:
        with pytest.raises(RuntimeError, match="reports no memory limit"):
            C._stack_budget()
    else:
        assert C._stack_budget() == expect


def test_info_reports_device_facts(tmp_path):
    """GET /info carries platform, kind and count as JAX reports them,
    whether the router is pinned to the host, and whether the native
    kernels are live — chip_smoke.py copies these, it never assumes."""
    import jax

    from pilosa_tpu import native
    from pilosa_tpu.server import Server
    from pilosa_tpu.utils.config import Config

    s = Server(Config(bind="127.0.0.1:0", data_dir=str(tmp_path / "d"),
                      anti_entropy_interval=0, route_mode="host"))
    s.open()
    try:
        diag = json.loads(
            urllib.request.urlopen(f"{s.uri}/info").read()
        )["diagnostics"]
    finally:
        s.close()
    dev = jax.local_devices()
    assert diag["backend"] == dev[0].platform == "cpu"
    assert diag["device_kind"] == dev[0].device_kind
    assert diag["device_count"] == len(dev)
    assert diag["router_pinned_host"] is True
    assert diag["native_kernels"] is native.available()
    assert diag["compile_cache_dir"] == jax.config.jax_compilation_cache_dir


# ------------------------------------------------------ chip_smoke.py
@pytest.fixture
def smoke(monkeypatch, tmp_path):
    """chip_smoke.py as a module; its server child keeps a compile cache
    of its own (the suite's workers share the checkout's)."""
    monkeypatch.syspath_prepend(REPO)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    import chip_smoke

    return chip_smoke


def test_chip_smoke_rehearsal(smoke, capsys):
    """Every phase at a tiny size with the platform it should find passed
    as an argument: load over the bulk route, each read equal to the
    numpy reference, the device engine serving all of them, the tiered
    container stores, acknowledged writes read back across a restart
    that compiles nothing."""
    facts = smoke.run(shards=4, residency_shards=4, seed=7, mesh=False,
                      expect_platform="cpu", stack_budget_bytes=4_000_000)
    assert facts["platform"] == "cpu"
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    phases = [x["phase"] for x in lines]
    assert phases[0] == "start" and phases.count("boot") == 2
    reads = [x for x in lines if x["phase"] == "read"]
    assert len(reads) == len(smoke.READS) + 2 * len(smoke.REREADS)
    assert all(x["equal"] for x in reads)
    routed = [x for x in lines if x["phase"] == "routed"]
    assert all(x["host"] == 0 and x["device"] == x["reads_issued"] for x in routed)
    tiered = next(x for x in lines if x["phase"] == "tiered")
    assert tiered["rows_promoted"] >= 6 and tiered["cold_uploads"] > 0
    cold, warm = (x for x in lines if x["phase"] == "compiles")
    assert cold["compiled"] > 0 and cold["persistent_cache_hits"] == 0
    assert warm["compiled"] == 0
    assert warm["compile_cache_entries"] == cold["compile_cache_entries"]


def test_chip_smoke_mesh_rehearsal(smoke, capsys):
    """--mesh on the suite's virtual CPU devices: every read takes the
    mesh route and the stacks are partitioned, not replicated."""
    import jax

    n = jax.local_device_count()
    facts = smoke.run(shards=2 * n, residency_shards=4, seed=7, mesh=True,
                      expect_platform="cpu", expect_count=n)
    assert facts["count"] == n
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    routed = next(x for x in lines if x["phase"] == "routed")
    assert routed["mesh"] == routed["reads_issued"] == len(smoke.READS)
    placed = next(x for x in lines if x["phase"] == "residency")
    assert placed["devices_spanned"] == n and placed["replicated_stacks"] == 0


def test_chip_smoke_command_fails_without_a_chip(tmp_path):
    """As the driver runs it: no accelerator → non-zero, no ok line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "need 'tpu'" in proc.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
