"""SPMD perf smoke: five benchmark-like query shapes on the 8-device
virtual CPU mesh with words_axis=2.

The benchmark runs only on the chip; between chip runs nothing else
exercises the SERVING-path SPMD programs at such query shapes, so a
sharding/layout regression (e.g. a stack losing its NamedSharding, a
reduction stopping being a collective) would surface only there. This
suite compiles and runs each shape over a (4 shards x 2 words) mesh at
tiny scale and asserts exact results — correctness here means the
psum/all_gather wiring is right, and compiling at all means the layouts
are mesh-legal.
"""

import numpy as np
import pytest

import jax

from pilosa_tpu.core import Holder
from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.parallel.mesh import MeshContext, make_mesh
from pilosa_tpu.shardwidth import SHARD_WIDTH


@pytest.fixture(scope="module")
def rig():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual platform")
    ctx = MeshContext(make_mesh(jax.devices()[:8], words_axis=2))
    h = Holder(None)
    idx = h.create_index("b")
    f = idx.create_field("f")
    g = idx.create_field("g")
    from pilosa_tpu.core.field import FIELD_INT, FieldOptions

    v = idx.create_field("v", FieldOptions(field_type=FIELD_INT, min=0, max=1000))
    rng = np.random.default_rng(7)
    n = 4000
    n_shards = 4
    cols = rng.integers(0, n_shards * SHARD_WIDTH, n).astype(np.uint64)
    frows = rng.integers(0, 8, n).astype(np.uint64)
    grows = rng.integers(0, 5, n).astype(np.uint64)
    f.import_bulk(frows, cols)
    g.import_bulk(grows, cols)
    vcols = np.unique(cols)
    vals = rng.integers(0, 1000, vcols.size).astype(np.int64)
    v.import_values(vcols, vals)
    e = Executor(h, mesh_ctx=ctx)
    truth = {}
    truth["pairs"] = set(zip(frows.tolist(), cols.tolist()))
    truth["gpairs"] = set(zip(grows.tolist(), cols.tolist()))
    truth["vals"] = dict(zip(vcols.tolist(), vals.tolist()))
    return e, truth


def _row_cols(truth, key, r):
    return {c for rr, c in truth[key] if rr == r}


def test_config1_intersect_count(rig):
    e, truth = rig
    got = e.execute("b", "Count(Intersect(Row(f=1), Row(g=2)))")[0]
    assert got == len(_row_cols(truth, "pairs", 1) & _row_cols(truth, "gpairs", 2))


def test_config2_multi_shard_setops(rig):
    e, truth = rig
    expect = (
        (_row_cols(truth, "pairs", 1) | _row_cols(truth, "pairs", 2))
        - _row_cols(truth, "gpairs", 0)
    ) ^ _row_cols(truth, "gpairs", 3)
    got = e.execute(
        "b",
        "Count(Xor(Difference(Union(Row(f=1), Row(f=2)), Row(g=0)), Row(g=3)))",
    )[0]
    assert got == len(expect)


def test_config3_topn_groupby(rig):
    e, truth = rig
    topn = e.execute("b", "TopN(f, n=3)")[0]
    counts = {r: len(_row_cols(truth, "pairs", r)) for r in range(8)}
    expect = sorted(counts.items(), key=lambda rc: (-rc[1], rc[0]))[:3]
    assert [(t["id"], t["count"]) for t in topn] == expect

    gb = e.execute("b", "GroupBy(Rows(f), Rows(g))")[0]
    expect_gb = {}
    for fr in range(8):
        fc = _row_cols(truth, "pairs", fr)
        for gr in range(5):
            c = len(fc & _row_cols(truth, "gpairs", gr))
            if c:
                expect_gb[(fr, gr)] = c
    got_gb = {
        (x["group"][0]["rowID"], x["group"][1]["rowID"]): x["count"] for x in gb
    }
    assert got_gb == expect_gb


def test_config4_bsi_sum_range(rig):
    e, truth = rig
    s = e.execute("b", "Sum(field=v)")[0]
    assert s["value"] == sum(truth["vals"].values())
    assert s["count"] == len(truth["vals"])
    got = e.execute("b", "Count(Row(v > 500))")[0]
    assert got == sum(1 for x in truth["vals"].values() if x > 500)


def test_config5_tanimoto_shape(rig):
    e, truth = rig
    # the tanimoto config reduces to intersect/union count ratios
    inter = e.execute("b", "Count(Intersect(Row(f=1), Row(f=2)))")[0]
    union = e.execute("b", "Count(Union(Row(f=1), Row(f=2)))")[0]
    a, b = _row_cols(truth, "pairs", 1), _row_cols(truth, "pairs", 2)
    assert inter == len(a & b) and union == len(a | b)


def test_stacks_sharded_over_both_axes(rig):
    e, truth = rig
    from pilosa_tpu.core.view import VIEW_STANDARD

    idx = e.holder.index("b")
    f = idx.field("f")
    m, _ = e.compiler.stacks.matrix(idx, f, VIEW_STANDARD, [0, 1, 2, 3])
    assert len(m.sharding.device_set) == 8, (
        "serving stack lost its (shards x words) NamedSharding"
    )


def test_qps_vs_device_count_curve(capsys):
    """QPS-vs-device-count curve over the virtual platform (ISSUE 2
    satellite): the same executor Count shape on 1/2/4/8-device meshes.
    On virtual CPU devices the absolute numbers are meaningless — what
    the curve proves is that every mesh width compiles, executes
    EXACTLY, and emits a machine-readable scaling record (the real-chip
    analogue is read off the MULTICHIP artifact)."""
    import json
    import time

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual platform")
    rng = np.random.default_rng(11)
    n_shards = 8
    n = 4000
    cols = rng.integers(0, n_shards * SHARD_WIDTH, n).astype(np.uint64)
    rows = rng.integers(0, 4, n).astype(np.uint64)
    expect = len({c for r, c in zip(rows.tolist(), cols.tolist()) if r in (1, 2)})

    curve = []
    for n_dev in (1, 2, 4, 8):
        ctx = MeshContext(make_mesh(jax.devices()[:n_dev], words_axis=1))
        h = Holder(None)
        idx = h.create_index("b")
        f = idx.create_field("f")
        f.import_bulk(rows, cols)
        e = Executor(h, mesh_ctx=ctx, route_mode="device")
        pql = "Count(Union(Row(f=1), Row(f=2)))"
        got = e.execute("b", pql)[0]
        assert got == expect, (n_dev, got, expect)
        iters = 20
        t0 = time.perf_counter()
        for _ in range(iters):
            e.execute("b", pql)
        qps = iters / (time.perf_counter() - t0)
        curve.append({"devices": n_dev, "qps": round(qps, 1)})
    assert all(pt["qps"] > 0 for pt in curve)
    # machine-readable record for the smoke artifact (driver greps stdout)
    with capsys.disabled():
        print(json.dumps({"metric": "spmd_qps_vs_devices", "curve": curve}),
              flush=True)


@pytest.mark.parametrize("n_devices,words_axis", [(16, 4), (32, 8), (64, 8)])
def test_dryrun_multichip_pod_shape(n_devices, words_axis):
    """VERDICT r4 next #9 + ISSUE 2 satellite: the multi-chip dry run
    must stay green at pod-shaped 16-, 32- and 64-device virtual meshes
    (words_axis 4 and 8 — words is the minor/ICI axis, shards the
    major/DCN axis; at 64 devices the grid is 8×8 with a multihost-style
    contiguous-words-row assertion inside dryrun_multichip), including
    the scaled-down BASELINE config-5 Tanimoto search. Runs in a
    subprocess because the in-process backend is pinned to 8 virtual
    devices by conftest."""
    import os
    import subprocess
    import sys

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
    )
    # the axis dryrun_multichip SELECTS must be the pod-shape one —
    # asserted against the selection function itself, not a tautological
    # make_mesh(words_axis=W) reshape
    proc = subprocess.run(
        [sys.executable, "-c",
         f"import __graft_entry__ as g; "
         f"assert g._pod_words_axis({n_devices}) == {words_axis}, "
         f"g._pod_words_axis({n_devices}); "
         f"g.dryrun_multichip({n_devices})"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
