"""An aggregate over an int field is ONE device program (PR 33).

``Executor._bsi_stacked`` used to pad the resident BSI stack to the
field's declared depth with an EAGER ``jnp.pad`` (and slice it with an
eager ``m[:need]``) before every ``Sum``, ``Min``, ``Max`` and
``GroupBy(aggregate=Sum)``: a second XLA program a query, launched from
Python on the wave leader's thread. Now the stack goes into the program
as it lies in memory and the depth rule is ``ops.bsi.block``, applied at
trace time inside the program. Held here, on the device route and on the
mesh route over the suite's virtual devices, for three int fields whose
resident stack is shallower than, equal to and deeper than
``BSI_OFFSET + bit_depth``:

(a) the answer equals the host engine's, and every program compiled
    during the call is a ``pilosa_*`` one;
(b) ``_bsi_stacked`` hands out the stack cache's own array, and with
    jax's caches emptied a ``Sum`` compiles its own program and no other;
(c) a write that deepens the stack between two ``Sum``s is read back
    exactly by the second.
"""

import json
import logging
import re

import jax
import numpy as np
import pytest

from pilosa_tpu import ops
from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import BSI_OFFSET, FIELD_INT, VIEW_BSI, FieldOptions
from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.parallel.mesh import MeshContext, make_mesh
from pilosa_tpu.shardwidth import SHARD_WIDTH

N_SHARDS = 8
INDEX = "one"
# field → (declared magnitude bits, magnitude bits the values fill): the
# stack's height is the filled planes' count rounded up to a power of two
FIELDS = {
    "shallow": (17, 9),  # 11 planes hold data, 16 resident, 19 declared
    "equal": (14, 14),  # 16, 16, 16
    "deep": (9, 9),  # 11 planes hold data, 16 resident, 11 declared
}
ROUTES = ["device", "mesh"]
THRESHOLD = 37


def _int_field(idx, name: str, declared: int):
    top = (1 << declared) - 1
    return idx.create_field(
        name, FieldOptions(field_type=FIELD_INT, min=-top, max=top)
    )


@pytest.fixture(scope="module")
def rig():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    rng = np.random.default_rng(33)
    h = Holder(None)
    idx = h.create_index(INDEX)
    n = 3000
    cols = rng.choice(N_SHARDS * SHARD_WIDTH, n, replace=False).astype(np.uint64)
    idx.create_field("f").import_bulk(rng.integers(0, 6, n).astype(np.uint64), cols)
    idx.create_field("g").import_bulk(rng.integers(0, 3, n).astype(np.uint64), cols)
    for name, (declared, filled) in FIELDS.items():
        top = (1 << filled) - 1
        vals = rng.integers(-top, top + 1, n).astype(np.int64)
        vals[:2] = (top, -top)  # the highest filled plane holds a bit
        _int_field(idx, name, declared).import_values(cols[: n - 200], vals[: n - 200])
    idx.mark_columns_exist(cols)
    engines = {
        "holder": h,
        "host": Executor(h, route_mode="host"),
        "device": Executor(h, route_mode="device"),
        "mesh": Executor(
            h,
            mesh_ctx=MeshContext(make_mesh(jax.devices(), words_axis=1)),
            route_mode="mesh",
        ),
    }
    for route in ROUTES:
        # what a call without an aggregate builds once and keeps: the
        # all-ones filter and GroupBy's leading axis on its base mask
        engines[route].execute(INDEX, "GroupBy(Rows(f))")
    return engines


def test_the_three_stacks_are_what_the_cases_say(rig):
    """Shallower than, equal to and deeper than the declared depth."""
    e, idx = rig["device"], rig["holder"].index(INDEX)
    heights = {}
    for name in FIELDS:
        field = idx.field(name)
        stack = e._bsi_stacked(idx, field, list(range(N_SHARDS)))
        heights[name] = (stack.shape[0], BSI_OFFSET + field.bit_depth)
    assert heights == {"shallow": (16, 19), "equal": (16, 16), "deep": (16, 11)}


class CompiledPrograms(logging.Handler):
    """Names of the programs jax compiles while attached, from the lines
    ``jax.log_compiles`` writes before each backend compile (they are
    written whether or not the persistent cache then has the program)."""

    LINE = re.compile(r"^Compiling (?:jit\()?([^\s()]+)\)? with global shapes")

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names: list[str] = []

    def emit(self, record):
        m = self.LINE.match(record.getMessage())
        if m:
            self.names.append(m.group(1))

    def __enter__(self):
        self._logger = logging.getLogger("jax")
        self._logger.addHandler(self)
        self._ctx = jax.log_compiles()
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        self._ctx.__exit__(*exc)
        self._logger.removeHandler(self)


def _norm(results) -> str:
    return json.dumps(results, sort_keys=True, default=str)


def _query(call: str, filt: str, field: str) -> str:
    row = {"none": None, "row": "Row(g=1)", "range": f"Row({field} > {THRESHOLD})"}[filt]
    if call == "GroupBy":
        tail = f", filter={row}" if row else ""
        return f"GroupBy(Rows(f){tail}, aggregate=Sum(field={field}))"
    return f"{call}({row + ', ' if row else ''}field={field})"


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("field", list(FIELDS))
@pytest.mark.parametrize("filt", ["none", "row", "range"])
@pytest.mark.parametrize("call", ["Sum", "Min", "Max", "GroupBy"])
def test_an_aggregate_equals_the_host_engine_and_compiles_only_its_own(
    rig, call, filt, field, route
):
    q = _query(call, filt, field)
    expect = _norm(rig["host"].execute(INDEX, q))
    with CompiledPrograms() as seen:
        got = _norm(rig[route].execute(INDEX, q))
    assert got == expect, f"{route} route diverged from the host engine on {q}"
    eager = [n for n in seen.names if not n.startswith("pilosa_")]
    assert not eager, f"{q} on the {route} route compiled {eager}"


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("field", list(FIELDS))
def test_the_stack_goes_in_as_it_lies_in_memory(rig, field, route):
    """No device operation between the stack cache and the program: the
    array is the cache's own, and from empty caches a ``Sum`` compiles
    ``pilosa_*`` programs alone (non-vacuously: it does compile)."""
    e, idx = rig[route], rig["holder"].index(INDEX)
    f, shards = idx.field(field), list(range(N_SHARDS))
    resident = e.compiler.stacks.matrix(idx, f, VIEW_BSI, shards)[0]
    assert e._bsi_stacked(idx, f, shards) is resident
    q = f"Sum(Row(g=2), field={field})"
    expect = _norm(rig["host"].execute(INDEX, q))
    jax.clear_caches()
    with CompiledPrograms() as seen:
        got = _norm(e.execute(INDEX, q))
    assert got == expect
    assert seen.names and all(n.startswith("pilosa_") for n in seen.names), seen.names
    want = "pilosa_mesh_sum" if route == "mesh" else "pilosa_sum_filtered"
    assert want in seen.names


def test_block_is_the_one_depth_rule():
    """Deeper: cut to the declared planes; shallower or equal: untouched
    (the same object, so nothing is traced for it)."""
    m = np.arange(16 * 2 * 4, dtype=np.uint32).reshape(16, 2, 4)
    assert ops.bsi.block(m, 19) is m
    assert ops.bsi.block(m, 16) is m
    assert np.array_equal(ops.bsi.block(m, 11), m[:11])


@pytest.mark.parametrize("route", ROUTES)
def test_a_write_that_deepens_the_stack_is_read_back_by_the_next_sum(route):
    """8 resident planes, then a value whose top bit lies in plane 14:
    the stack is rebuilt 16 deep, the sum retraces for the new shape by
    itself, and the acknowledged write is in the answer."""
    h = Holder(None)
    idx = h.create_index("grow")
    v = _int_field(idx, "v", 17)
    cols = np.arange(0, N_SHARDS * SHARD_WIDTH, SHARD_WIDTH // 4, dtype=np.uint64)
    vals = (np.arange(len(cols)) % 31).astype(np.int64) - 9
    v.import_values(cols, vals)
    idx.mark_columns_exist(cols)
    if route == "mesh":
        e = Executor(
            h,
            mesh_ctx=MeshContext(make_mesh(jax.devices(), words_axis=1)),
            route_mode="mesh",
        )
    else:
        e = Executor(h, route_mode="device")
    shards = list(range(N_SHARDS))
    assert e._bsi_stacked(idx, v, shards).shape[0] == 8
    first = e.execute("grow", "Sum(field=v)")[0]
    assert first == {"value": int(vals.sum()), "count": len(cols)}
    col = int(cols[5]) + 1
    assert e.execute("grow", f"Set({col}, v=4099)") == [True]
    assert e._bsi_stacked(idx, v, shards).shape[0] == 16
    with CompiledPrograms() as seen:
        second = e.execute("grow", "Sum(field=v)")[0]
    assert second == {"value": int(vals.sum()) + 4099, "count": len(cols) + 1}
    assert all(n.startswith("pilosa_") for n in seen.names), seen.names
    for q in ("Max(field=v)", "Min(field=v)", "Count(Row(v > 4098))"):
        assert _norm(e.execute("grow", q)) == _norm(
            Executor(h, route_mode="host").execute("grow", q)
        ), q
