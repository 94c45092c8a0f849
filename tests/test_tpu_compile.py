"""The programs chip_smoke.py runs, compiled for a DESCRIBED TPU v5e at
the smoke's real shapes (256 shards × 32768 words) — no chip attached,
nothing executes (on-chip-measurement guide §2, rehearsal 3).

Every program is the query compiler's own: the smoke's queries run once
here on the CPU at a tiny size while a recorder keeps each jitted program
the compiler built together with the arguments it was called with; the
same program objects are then lowered for the described chip with those
arguments scaled to the real size. XLA's TPU compiler raises here what it
would raise on the chip (unsupported ops, programs that do not fit HBM).

The topology is described inside a module-scoped fixture, never at
import: only the xdist worker that runs this file loads libtpu, and it
keeps it until it exits — so every compile happens in this process, and
all of them live in this one file.
"""

import os

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from pilosa_tpu import ops
from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.executor import Executor, compile as query_compile, executor as executor_mod
from pilosa_tpu.executor.compile import QueryCompiler
from pilosa_tpu.executor.residency import RUN_MAX_INTERVALS, SPARSE_MAX_IDS
from pilosa_tpu.parallel.mesh import MeshQueryEngine
from pilosa_tpu.pql import parse
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD

# chip_smoke.py's size: SHARDS, RESIDENCY_SHARDS, the production width
S, W, S_RARE = 256, 1 << 15, 64
S_TINY = 3  # shards of the CPU run; differs from every other dimension
HBM_BYTES = 16 * 10**9  # one v5e chip
# dense stacks the smoke keeps resident beside any one program's inputs:
# cab_type, passenger_count, existence at 8 padded rows and fare at 32
RESIDENT_BYTES = (8 + 8 + 8 + 32) * S * W * 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    """The (shards × words) serving mesh over the four described chips."""
    return Mesh(np.array(topo.devices).reshape(4, 1), ("shards", "words"))


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns and
    recompiles), so the cache is off around this file's compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def rig():
    """The smoke's schema at a tiny size on the CPU, device route pinned."""
    h = Holder(None)
    idx = h.create_index("taxi")
    cab = idx.create_field("cab_type")
    pas = idx.create_field("passenger_count")
    fare = idx.create_field(
        "fare", FieldOptions(field_type="int", min=0, max=65535)
    )
    rng = np.random.default_rng(0)
    n = 4000
    cols = rng.choice(S_TINY * SHARD_WIDTH, n, replace=False).astype(np.uint64)
    cab.import_bulk(rng.integers(0, 5, n).astype(np.uint64), cols)
    pas.import_bulk(rng.integers(0, 8, n).astype(np.uint64), cols)
    fare.import_values(cols, rng.integers(0, 1 << 16, n))
    # the benchmark's int field: 17 bits declared, 11 filled, so 13 of
    # the 16 resident planes hold data and the field declares 19
    amount = idx.create_field(
        "amount", FieldOptions(field_type="int", min=0, max=(1 << 17) - 1)
    )
    amount.import_values(cols, rng.integers(0, 1 << 11, n))
    # the GroupBy cell's three grouped fields at their row counts: 10
    # passenger rows (16 padded), 8 years, 32 rounded distances
    for name, k in (("riders", 10), ("year", 8), ("miles", 32)):
        idx.create_field(name).import_bulk(rng.integers(0, k, n).astype(np.uint64), cols)
    idx.mark_columns_exist(cols)
    e = Executor(h, route_mode="device")
    # GroupBy chunks its [G, S, W] group masks to an eighth of the stack
    # budget (70 % of HBM): hold the tiny run to as many PLANES as the
    # chip's budget holds at the real size, so it chunks the same way
    real_planes = int(HBM_BYTES * 0.7) // 8 // (S * W * 4)
    e.GROUPBY_MASK_BUDGET = real_planes * S_TINY * WORDS_PER_SHARD * 4
    return h, idx, e


def record(monkeypatch, executor, pql: str) -> list[tuple]:
    """Run ``pql`` on the CPU; → [(jitted program, args)] for every
    program the query compiler built or reused for it."""
    calls: list[tuple] = []
    original = QueryCompiler.program

    def program(self, key, build):
        prog = original(self, key, build)

        def recorder(*args):
            calls.append((prog, args))
            return prog(*args)

        return recorder

    monkeypatch.setattr(QueryCompiler, "program", program)
    executor.execute("taxi", pql)
    monkeypatch.setattr(QueryCompiler, "program", original)
    assert calls, f"{pql} ran no compiled program"
    return calls


def real_size(args, sharding_of, shards: int = S):
    """The recorded arguments as shapes at the smoke's real size:
    trailing [S_TINY, W_test] plane dimensions become [shards, W]."""

    def one(x):
        if not isinstance(x, (np.ndarray, jax.Array)):
            return x
        shape = tuple(x.shape)
        if shape[-2:] == (S_TINY, WORDS_PER_SHARD):
            shape = shape[:-2] + (shards, W)
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding_of(shape))

    return jax.tree_util.tree_map(one, args)


def shapes_on(sharding):
    """(shape, dtype) → an abstract argument placed with ``sharding``."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compile_and_fit(prog, args, devices: int = 1):
    """Compile for the described chip(s); the program's own arguments,
    temporaries and outputs must fit HBM beside the resident stacks."""
    compiled = prog.lower(*args).compile()
    mem = compiled.memory_analysis()
    need = (
        mem.argument_size_in_bytes
        + mem.temp_size_in_bytes
        + mem.output_size_in_bytes
        + RESIDENT_BYTES // devices
    )
    assert need < HBM_BYTES, f"needs {need / 2**30:.1f} GiB of a 16 GB chip: {mem}"
    return compiled


@pytest.mark.parametrize(
    "pql",
    [
        "Count(Intersect(Row(cab_type=0), Row(passenger_count=1)))",
        "Count(Union(Row(cab_type=1), Row(cab_type=3), Row(passenger_count=4)))",
        "Count(Difference(Row(cab_type=0), Row(passenger_count=1)))",
        "Count(Not(Row(cab_type=0)))",
    ],
)
def test_count_over_set_op_tree(rig, one_chip, monkeypatch, pql):
    _h, _idx, e = rig
    for prog, args in record(monkeypatch, e, pql):
        compile_and_fit(prog, real_size(args, lambda _s: one_chip))


def test_topn_with_filter(rig, one_chip, monkeypatch):
    _h, _idx, e = rig
    for prog, args in record(
        monkeypatch, e, "TopN(passenger_count, Row(cab_type=1), n=4)"
    ):
        compile_and_fit(prog, real_size(args, lambda _s: one_chip))


@pytest.mark.parametrize(
    "pql",
    [
        "Sum(Row(cab_type=2), field=fare)",
        "Min(field=fare)",
        "Count(Row(fare > 20000))",
        "Count(Row(1000 <= fare <= 30000))",
    ],
)
def test_bsi_sum_minmax_and_range(rig, one_chip, monkeypatch, pql):
    """int64 reductions and the bit-sliced compares over [32, S, W]."""
    _h, _idx, e = rig
    for prog, args in record(monkeypatch, e, pql):
        compile_and_fit(prog, real_size(args, lambda _s: one_chip))


def test_sum_reads_the_resident_stack_and_nothing_of_a_pad(rig, one_chip, monkeypatch):
    """Q2 of the cell taxi-128.four_queries: ``pilosa_sum_filtered`` takes
    the amount's stack as resident, [16, 128, W] under a field that
    declares 19 planes, and the compiled program reads those 16 planes
    once: no [19, S, W] block anywhere in it, no plane-sized temporary in
    HBM. Bytes by XLA's own count: the 16 planes, and 7 more for the two
    candidate masks (made from the existence, sign and filter rows,
    written and read again by the reduction) and the count's two rows;
    padded to the declared depth it read 26."""
    _h, _idx, e = rig
    calls = record(monkeypatch, e, "Sum(Row(passenger_count=2), field=amount)")
    assert len(calls) == 1
    prog, args = calls[0]
    assert args[0].shape[0] == 16  # the tiny run's stack is as deep as the cell's
    compiled = compile_and_fit(prog, real_size(args, lambda _s: one_chip, shards=128))
    text = compiled.as_text()
    assert "jit(pilosa_sum_filtered)" in text
    assert "u32[16,128,32768]" in text and "u32[19," not in text and "pad(" not in text
    plane = 128 * W * 4
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["bytes accessed"] <= 24 * plane, cost["bytes accessed"] / plane
    assert compiled.memory_analysis().temp_size_in_bytes < plane


def test_groupby_level_with_sum(rig, one_chip, monkeypatch):
    """One GroupBy level: the count pass, the surviving-mask pass and
    aggregate=Sum over the 5 × 8 group masks, chunked as the mask
    budget chunks them at the real size."""
    _h, _idx, e = rig
    calls = record(
        monkeypatch, e,
        "GroupBy(Rows(cab_type), Rows(passenger_count), "
        "aggregate=Sum(field=fare))",
    )
    for prog, args in calls:  # gb_sums: (slices [D,S,W], masks [G,S,W])
        compile_and_fit(prog, real_size(args, lambda _s: one_chip))
    sds = shapes_on(one_chip)
    masks, matrix = sds((8, S, W), np.uint32), sds((8, S, W), np.uint32)
    compile_and_fit(
        executor_mod._gb_counts, (masks, matrix, sds((8,), np.int32))
    )
    compile_and_fit(
        executor_mod._gb_masks,
        (masks, matrix, sds((16,), np.int32), sds((16,), np.int32)),
    )


@pytest.mark.parametrize("groups", [64, 16])
def test_groupby_programs_at_the_groupby_cells_shapes(rig, one_chip, groups):
    """The cell taxi-128g.groupby_fare: 128 shards, the fourth query's
    level-synchronous chunks of 64 + 16 (passenger, year) masks against
    the 32 distance rows, and the second query's grouped sum over the
    16-plane amount block. What a GroupBy holds on the device is counted
    by the transient ledger as masks + ``TEMP_PLANES``: XLA's own
    ``temp_size_in_bytes`` of every program has to stay inside that, and
    the fourth query's whole need inside the chip's transient budget."""
    _h, idx, e = rig
    shards, plane = 128, 128 * W * 4
    sds = shapes_on(one_chip)
    allowed = ops.groupby.TEMP_PLANES * plane
    parents, years, miles = sds((16, shards, W), np.uint32), sds((8, shards, W), np.uint32), \
        sds((32, shards, W), np.uint32)
    masks = sds((groups, shards, W), np.uint32)
    counts = compile_and_fit(executor_mod._gb_counts, (masks, miles, sds((32,), np.int32)))
    assert counts.memory_analysis().temp_size_in_bytes <= allowed
    made = compile_and_fit(
        executor_mod._gb_masks,
        (parents, years, sds((groups,), np.int32), sds((groups,), np.int32)),
    )
    mem = made.memory_analysis()
    assert mem.output_size_in_bytes == groups * plane
    assert mem.temp_size_in_bytes <= allowed
    root = compile_and_fit(  # a level's first launch: the filter's one plane
        executor_mod._gb_counts, (sds((shards, W), np.uint32), parents, sds((16,), np.int32))
    )
    assert root.memory_analysis().temp_size_in_bytes <= allowed
    sums = compile_and_fit(
        e._grouped_sum_program(idx.field("amount"), shards),
        (sds((16, shards, W), np.uint32), sds((16, shards, W), np.uint32)),
    )
    assert sums.memory_analysis().temp_size_in_bytes <= allowed
    # the fourth query at its fullest: the filter's plane, 16 passenger
    # masks, a chunk of 64 pair masks, the temporaries
    budget = int(16.9e9 * 0.7) // 8
    assert (1 + 16 + 64 + ops.groupby.TEMP_PLANES) * plane <= budget


@pytest.mark.parametrize(
    "groups,stack,rows",
    [(32, 1024, 1024), (8, 1024, 1024), (16, 256, 256), (1, 256, 256), (128, 8, 8), (8, 32, 32),
     (8, 1024, 64), (32, 1024, 64), (1, 1024, 1024)],
    ids=["q4_3", "q2_x", "q3_2", "q3_root", "q3_2_years", "q3_1", "q2_x_held", "q4_3_held",
         "brands_root"],
)
def test_count_pass_at_the_ssb_cells_shapes(one_chip, groups, stack, rows):
    """The cell ssb-24.flights: 24 shards, the level walk's counts launches
    (Q4.3's 32 (year, city) masks against the 1,024 brands, Q2.x's 8 years
    against them, Q3.2's 16 cities against 256, 128 (city, city) masks
    against the 8 years; the 64 brands the filter holds of Q2.x and Q4.3;
    the first read's filter against the 1,024 brands). Temporaries inside ``TEMP_PLANES`` whatever the
    level's rows and masks: whole, the 1,024 rows' block of shards was
    683 planes (2 GiB) that the transient ledger did not count."""
    shards = 24
    plane = shards * W * 4
    sds = shapes_on(one_chip)
    masks = sds((groups, shards, W) if groups > 1 else (shards, W), np.uint32)
    counts = compile_and_fit(
        executor_mod._gb_counts, (masks, sds((stack, shards, W), np.uint32), sds((rows,), np.int32))
    )
    assert counts.memory_analysis().temp_size_in_bytes <= ops.groupby.TEMP_PLANES * plane


@pytest.mark.parametrize("stack", [1024, 256], ids=["brands", "cities"])
def test_first_read_is_one_pass_over_the_whole_stack(one_chip, stack):
    """ssb-24.flights' first read, the filter's one mask against every row
    of the 1,024 brands or the 256 cities (``ops.groupby.whole_stack``),
    compiles to one reduction over the stack: no loop of tiles, no block
    of rows gathered into a buffer (a dynamic-update-slice), and no
    temporaries beyond a plane."""
    shards = 24
    sds = shapes_on(one_chip)
    args = (sds((shards, W), np.uint32), sds((stack, shards, W), np.uint32), sds((stack,), np.int32))
    assert ops.groupby.whole_stack(*args)
    compiled = compile_and_fit(executor_mod._gb_counts, args)
    text = compiled.as_text()
    assert " while(" not in text and "dynamic-update-slice(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= ops.groupby.TEMP_PLANES * shards * W * 4


def test_taxi_128g_shapes_keep_their_tiles():
    """Every counts launch of taxi-128g.groupby_fare at 128 shards (a
    ``g1``'s filter against 4 cab types, a ``g2``'s against 16 passenger
    counts, the level walk's root against 8 or 32 rows and its 16 and 64
    masks against 32 distances) stays in the one tile that holds its rows:
    ``whole_stack`` is false, so its programs are the ones they were. Its
    grouped sums pass a sign plane (``within``) and never take it."""
    sds = lambda shape, dtype=np.uint32: jax.ShapeDtypeStruct(shape, dtype)
    shards = 128
    for groups, stack, k in [(1, 8, 4), (1, 16, 16), (1, 8, 8), (1, 32, 32), (16, 32, 32),
                             (64, 32, 32), (16, 16, 16)]:
        masks = sds((shards, W)) if groups == 1 else sds((groups, shards, W))
        assert not ops.groupby.whole_stack(masks, sds((stack, shards, W)), sds((k,), np.int32))


@pytest.mark.parametrize("groups,planes", [(128, 26), (256, 26), (1, 27)], ids=["q3_q4", "q2_x", "one"])
def test_grouped_sum_at_the_ssb_cells_shapes(one_chip, groups, planes):
    """The cell ssb-24.flights' grouped sums: a level walk's chunk of 128
    (Q3.x, Q4.x) or 256 (Q2.x) group masks against lo_revenue's 26
    planes at 24 shards, and one mask against lo_profit's 27. The count
    pass with the planes for rows, its temporaries inside ``TEMP_PLANES``
    whatever the groups."""
    shards = 24
    plane = shards * W * 4
    sds = shapes_on(one_chip)
    sums = compile_and_fit(
        executor_mod.named_jit("pilosa_sum_groups", ops.groupby.grouped_sums),
        (sds((planes, shards, W), np.uint32), sds((groups, shards, W), np.uint32)),
    )
    assert sums.memory_analysis().temp_size_in_bytes <= ops.groupby.TEMP_PLANES * plane


G2 = "GroupBy(Rows(riders), filter=Row(amount > 40), aggregate=Sum(field=amount))"
G3 = "GroupBy(Rows(riders), Rows(year), filter=Row(amount > 40))"
G4 = "GroupBy(Rows(riders), Rows(year), Rows(miles), filter=Row(amount > 40))"
# what the deferred walk launches for them at the cell's size, beside the
# filter: (program, its arguments' leading dimensions; a tuple of stacks
# as a tuple). A GroupBy of several levels without an aggregate is ONE
# chain count: the upper stacks and their padded rows, the (riders[,
# year]) chains of real rows [P, L-1], how many are real, the last
# level's stack and its padded rows. The walk
# with masks launched g4's masks [16], [64] and [16] and counts [64] x 32
# and [16] x 32 (the 64/16 programs of the level path, compiled above)
DEFERRED_LAUNCHES = {
    G2: [("counts", (), (16,), (16,)), ("masks", (), (16,), (16,), (16,)),
         ("sums", (16,), (16,))],
    G3: [("chains", (), ((16,),), ((16,),), (16, 1), (), (8,), (8,))],
    G4: [("chains", (), ((16,), (8,)), ((16,), (8,)), (128, 2), (), (32,), (32,))],
}


@pytest.mark.parametrize("pql", [G2, G3, G4], ids=["g2", "g3", "g4"])
def test_deferred_groupby_launches_at_the_groupby_cells_shapes(rig, one_chip, monkeypatch, pql):
    """The second, third and fourth query of taxi-128g.groupby_fare on
    the deferred walk, under the budget the cell's chip gives them (88
    planes of 128 shards): every program they launch, at the shapes they
    launch it with, compiles for the described v5e with its temporaries
    inside ``TEMP_PLANES``, the list of them is the one above, and a
    chain count reserves the filter's plane and the temporaries alone."""
    h, _idx, _e = rig
    shards, plane = 128, 128 * W * 4
    e = Executor(h, route_mode="device")
    e.GROUPBY_MASK_BUDGET = (int(16.9e9 * 0.7) // 8 // plane) * S_TINY * WORDS_PER_SHARD * 4
    launches: list[tuple] = []
    reserved: list[int] = []
    original, admit = Executor._gb_launch, executor_mod.GroupByLedger.admit

    def launch(self, what, prog, *args):
        if what != "filter":  # the planner's own program, compiled above
            launches.append((what, prog, args))
        return original(self, what, prog, *args)

    def admitted(self, nbytes, budget, **tags):
        reserved.append(nbytes // (S_TINY * WORDS_PER_SHARD * 4))
        # the admission span's labels: the device route, the planes reserved
        assert tags == {"devices": 1, "planes": reserved[-1]}
        return admit(self, nbytes, budget, **tags)

    monkeypatch.setattr(Executor, "_gb_launch", launch)
    monkeypatch.setattr(executor_mod.GroupByLedger, "admit", admitted)
    assert e.execute("taxi", pql)[0]
    monkeypatch.undo()
    # in planes: g4's walk with masks reserved 1 + 16 + 64 + TEMP_PLANES
    chain = launches[0][0] == "chains"
    assert reserved == [1 + (0 if chain else 16) + ops.groupby.TEMP_PLANES]

    def leading(x):
        if isinstance(x, tuple):
            return tuple(leading(a) for a in x)
        return tuple(x.shape[:-2]) if x.shape[-2:] == (S_TINY, WORDS_PER_SHARD) else tuple(x.shape)

    assert [(what,) + tuple(leading(a) for a in args) for what, _p, args in launches] \
        == DEFERRED_LAUNCHES[pql]
    seen = set()
    for what, prog, args in launches:
        sized = real_size(args, lambda _s: one_chip, shards=shards)
        key = (what,) + tuple(str(jax.tree_util.tree_map(np.shape, a)) for a in sized)
        if key in seen:
            continue
        seen.add(key)
        mem = compile_and_fit(prog, sized).memory_analysis()
        assert mem.temp_size_in_bytes <= ops.groupby.TEMP_PLANES * plane, key


def test_stack_delta_and_store_scatters(one_chip):
    """The write path: dirty rows scattered into the resident fare stack,
    and promoted rows scattered into the tiered container stores at the
    sizes the default budget gives them (70 % of 16 GB)."""
    sds = shapes_on(one_chip)
    compile_and_fit(
        query_compile._apply_stack_delta,
        (sds((32, S, W), np.uint32), sds((16, 2), np.int32),
         sds((16, W), np.uint32)),
    )
    stores = [
        ((512, S_RARE, W), np.uint32),  # dense planes, half the budget
        ((131072, SPARSE_MAX_IDS), np.int32),  # sparse ids, an eighth
        ((524288, RUN_MAX_INTERVALS, 2), np.int32),  # runs, a sixteenth
    ]
    for shape, dtype in stores:
        compile_and_fit(
            query_compile._scatter_rows,
            (sds(shape, dtype), sds((4,), np.int32),
             sds((4,) + shape[1:], dtype)),
        )


def test_tiered_container_decode(one_chip):
    """Sparse and run payloads decoded to [S, W] planes inside the
    consuming program, and the payload-only direct counts."""
    sds = shapes_on(one_chip)

    @jax.jit
    def intersect_count(ids, runs):
        words = ops.containers.sparse_plane(
            ids, S_RARE, W
        ) & ops.containers.run_plane(runs, S_RARE, W)
        return jax.numpy.sum(ops.popcount_rows(words).astype(jax.numpy.int64))

    ids = sds((SPARSE_MAX_IDS,), np.int32)
    runs = sds((RUN_MAX_INTERVALS, 2), np.int32)
    compile_and_fit(intersect_count, (ids, runs))
    compile_and_fit(jax.jit(ops.containers.sparse_count), (ids,))
    compile_and_fit(jax.jit(ops.containers.run_count), (runs,))


def placed_on(mesh):
    """shape → the sharding the stack cache gives it on ``mesh``: planes
    split along the shards axis, vectors of scalars replicated."""

    def placed(shape):
        spec = P(*(None,) * (len(shape) - 2), "shards", "words") if len(shape) > 1 else P()
        return NamedSharding(mesh, spec)

    return placed


def mesh_plan(rig, placed, pql: str, real_shards: int):
    """(planner closure traced against a chip's block, its arguments as
    shapes at ``real_shards`` shards over the four chips)."""
    _h, idx, e = rig
    planner = query_compile._Planner(
        idx, list(range(S_TINY)), e.compiler.stacks, block_shape=(real_shards // 4, W)
    )
    run, _skey = planner.plan(parse(pql)[0])
    arrays = planner.materialize()
    scalars = np.asarray(planner.scalar_values(), dtype=np.int32)
    return run, real_size((arrays, scalars), placed, shards=real_shards)


def test_mesh_count_and_topn(rig, mesh):
    """The shard_map Count and filtered-TopN builders on a 2×2 v5e mesh,
    stacks partitioned along the shards axis."""
    engine = MeshQueryEngine(mesh)
    placed = placed_on(mesh)

    run, args = mesh_plan(rig, placed, "Intersect(Row(cab_type=0), Row(passenger_count=1))", S)
    compiled = compile_and_fit(engine.count_tree(run, "grid"), args, devices=4)
    assert "all-reduce" in compiled.as_text()  # the psum tree over chips

    frun, (farrays, fscalars) = mesh_plan(rig, placed, "Row(cab_type=1)", S)
    matrix = jax.ShapeDtypeStruct((8, S, W), np.uint32, sharding=placed((8, S, W)))
    compile_and_fit(
        engine.topn_tree("grid", True, False, frun=frun),
        (matrix, farrays, fscalars),
        devices=4,
    )


# the cell taxi-512x4.four_queries (benchmark/configs/taxi-512x4.json):
# 512 shards over the four chips, dist_miles a 32-row stack, the amount's
# BSI stack as resident: 16 planes under a field that declares 19, cut to
# depth by ops.bsi.block inside the shard_map body
S_CELL = 512


@pytest.mark.parametrize("program", ["topn", "sum", "count", "chains", "counts"])
def test_mesh_programs_at_the_four_chip_cells_shapes(rig, mesh, program):
    """Q4's TopN over [32, 512, W] under a two-row filter, Q2's Sum over
    [16, 512, W] under a one-row filter, Q3's Count of an Intersect and a
    g4-shaped GroupBy's chain count ([128, 2] chains of the 16 and 8 row
    stacks against 32 rows) compile for the 4 x 1 v5e mesh, 128 shards a
    chip, and so does a level walk's first read at 32 shards a chip (one
    mask against 1,024 rows, tile by tile: the stack alone is 4 GiB a
    chip); the two GroupBy programs keep their temporaries inside
    ``TEMP_PLANES`` of a chip's plane. Their psum trees carry the scope
    the device trace is read by."""
    engine = MeshQueryEngine(mesh)
    placed = placed_on(mesh)
    plan = lambda pql: mesh_plan(rig, placed, pql, S_CELL)
    shards = S_CELL if program != "counts" else S_CELL // 4

    def stack(rows):
        shape = (rows, shards, W)
        return jax.ShapeDtypeStruct(shape, np.uint32, sharding=placed(shape))

    if program == "topn":
        frun, (farrays, fscalars) = plan("Intersect(Row(cab_type=1), Row(passenger_count=2))")
        prog = engine.topn_tree("grid", True, False, frun=frun)
        args = (stack(32), farrays, fscalars)
    elif program == "sum":
        frun, (farrays, fscalars) = plan("Row(passenger_count=2)")
        amount = rig[1].field("amount")
        prog = engine.sum_tree(Executor._sum_fn(amount), "grid", frun=frun)
        args = (stack(16), farrays, fscalars)
    elif program == "count":
        run, args = plan("Intersect(Row(cab_type=1), Row(passenger_count=2))")
        prog = engine.count_tree(run, "grid")
    else:
        scalars = lambda shape: jax.ShapeDtypeStruct(shape, np.int32, sharding=placed(()))
        if program == "chains":
            prog = engine.groupby_chains_tree("grid")
            args = (stack(1), (stack(16), stack(8)), (scalars((16,)), scalars((8,))),
                    scalars((128, 2)), scalars(()), stack(32), scalars((32,)))
        else:
            prog = engine.groupby_counts_tree("grid")
            args = (stack(1), stack(1024), scalars((1024,)))
    compiled = compile_and_fit(prog, args, devices=4)
    if program in ("chains", "counts"):
        chip_plane = shards // 4 * W * 4
        assert compiled.memory_analysis().temp_size_in_bytes <= ops.groupby.TEMP_PLANES * chip_plane
    text = compiled.as_text()
    assert "all-reduce" in text and "pilosa.mesh_psum" in text
    assert "u32[19," not in text


# the cell ssb-96x4.flights (benchmark/configs/ssb-96x4.json): 96 shards
# over the four chips, 24 a chip, ssb-24's one chip's share; a chip holds
# a quarter of every stack, 2,008 padded planes of 24 shards
S_SSB = 96
SSB_CHIP_STACKS = 2008 * (S_SSB // 4) * W * 4


@pytest.mark.parametrize(
    "program,groups,rows",
    [("counts", 32, 1024), ("counts", 8, 1024), ("counts", 16, 256), ("counts", 1, 1024),
     ("counts", 1, 256), ("masks", 32, 1024), ("sums", 128, 26), ("sums", 256, 26), ("sums", 1, 27)],
    ids=["q4_3", "q2_x", "q3_2", "brands_root", "cities_root", "q4_3_masks", "q3_q4_sums",
         "q2_x_sums", "one_sum"],
)
def test_mesh_groupby_programs_at_the_ssb_four_chip_cells_shapes(mesh, program, groups, rows):
    """The level walk's and the deferred walk's mesh programs at
    ssb-96x4.flights' shapes compile for the 4 x 1 v5e mesh: the counts
    of 32 (year, city) masks against the 1,024 brands, of 8 years against
    them, of 16 cities against 256, the first read's one mask against the
    1,024 brands or the 256 cities (one pass over the whole stack, as on
    one chip); a chunk of 128 pair masks from 32 parents and the brands;
    the grouped sums of 128 or 256 masks against lo_revenue's 26 planes
    and of one against lo_profit's 27. Temporaries inside ``TEMP_PLANES``
    of a chip's plane and the rows of one tile of the count pass
    (``ops.groupby.mesh_tile_bytes``), what the transient ledger reserves
    a device on the mesh route, and a chip's share of the stacks beside
    each program fits its memory."""
    engine = MeshQueryEngine(mesh)
    placed = placed_on(mesh)

    def planes(n):
        shape = (n, S_SSB, W)
        return jax.ShapeDtypeStruct(shape, np.uint32, sharding=placed(shape))

    def ids(n):
        return jax.ShapeDtypeStruct((n,), np.int32, sharding=placed(()))

    if program == "counts":
        prog = engine.groupby_counts_tree("grid")
        args = (planes(groups), planes(rows), ids(rows))
    elif program == "masks":
        prog = engine.groupby_masks_tree("grid")
        args = (planes(groups), planes(rows), ids(128), ids(128))
    else:
        prog = engine.grouped_sum_tree(ops.groupby.grouped_sums, "grid")
        args = (planes(rows), planes(groups))
    compiled = compile_and_fit(prog, args, devices=4)
    mem = compiled.memory_analysis()
    chip_plane = S_SSB // 4 * W * 4
    tile = ops.groupby.mesh_tile_bytes(S_SSB // 4, W)
    assert mem.temp_size_in_bytes <= ops.groupby.TEMP_PLANES * chip_plane + tile
    assert SSB_CHIP_STACKS + mem.temp_size_in_bytes + mem.output_size_in_bytes < HBM_BYTES * 0.7
    if program != "masks":
        text = compiled.as_text()
        assert "all-reduce" in text and "pilosa.mesh_psum" in text
