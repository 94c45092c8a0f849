"""Dataset ``taxi_range``: ``taxi``'s data, and a reference that can also
answer a range over the int field.

The generator is ``datasets/taxi.py``'s, imported unedited (``schema``,
``parts``, ``gen_shard``, the frames), so the server is loaded with the
same bytes as under ``taxi``. What differs is the reference's state: a
HISTOGRAM of the int field's values in every cell of the joint table,
``[row of set field 1, ..., row of set field k, amount] -> columns``,
accumulated while the shards are generated. The generator draws
``AMOUNTS`` distinct amounts from ``AMOUNT_MIN`` up, so the table has one
more axis of that length; the counts and the totals of ``taxi``'s table
are its sums along that axis.

``Row(field OP v)`` and ``Row(lo OP field OP hi)`` are then one more kind
of mask over the table, true for the amounts that meet the condition and
broadcast over every set field, and may stand anywhere in a row tree
under ``Count``, ``TopN``, ``Sum`` and ``GroupBy``. The answer comes from
the query TEXT, with nothing of the program's parser, planner, engines or
ops in it. Every column holds a value, so ``!=`` is the complement of
``==``. It has no rule for ``Min``/``Max``.

A configuration over this dataset may name, under ``requires``, what the
program has to have to serve it; ``schema`` fails the run before a byte
is loaded where the program lacks it (exit code 1, seconds after the
server is up). ``taxi-128r`` requires the counter that came with a
condition's constants as operands: a program that builds an XLA program
a threshold answers the traffic, at 2.6 queries/s and 3 GB of server
memory a run (PERF.md, PR 32), and is no server of this deployment.
"""

from __future__ import annotations

import operator

import numpy as np

from benchmark.datasets import taxi
from benchmark.datasets.taxi import drop_last_part, gen_shard, parts  # noqa: F401
from benchmark.harness.server import RunFailure

# gen_shard: amount = 3 + ((v * v) >> 22) with v < 2**16
AMOUNT_MIN, AMOUNTS = 3, 1024

_OPS = {"<": operator.lt, "<=": operator.le, ">": operator.gt, ">=": operator.ge,
        "==": operator.eq, "!=": operator.ne}
# beyond every amount on either side; keeps a huge constant inside int64
_FAR = 1 << 62


def schema(cfg: dict) -> list[tuple[str, bytes]]:
    """``taxi``'s schema, once the program is seen to register every
    ``/metrics`` family the configuration ``requires`` (the registry is
    ``pilosa_tpu/utils/stats.py``'s table of families, read without jax)."""
    from pilosa_tpu.utils import stats as program_stats

    known = getattr(program_stats, "_METRIC_HELP", {})
    for family in cfg.get("requires", {}).get("metrics_families", []):
        if family not in known:
            raise RunFailure(
                f"configuration {cfg['name']!r} requires a program that registers {family!r}: "
                + cfg["requires"]["why"]
            )
    return taxi.schema(cfg)


def load_part(base: str, index: str, seed: int, cfg: dict, mine: list[int]) -> dict:
    """``taxi.load_part`` with the histogram as the state: generate the
    shards in ``mine``, post them over the program's bulk route, count
    every column under (its cell, its amount)."""
    from pilosa_tpu import loader, roaring
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    luts = taxi._luts(cfg)
    counts = taxi._fields(cfg)[1]
    bins = int(np.prod(counts)) * AMOUNTS
    hist = np.zeros(bins, dtype=np.int64)

    def frames():
        for shard in mine:
            rows, amount = gen_shard(seed, shard, SHARD_WIDTH, luts)
            step = amount - AMOUNT_MIN
            if step.min() < 0 or step.max() >= AMOUNTS:
                raise ValueError("dataset taxi_range: an amount outside the histogram's axis")
            cell = np.ravel_multi_index([r.astype(np.intp) for r in rows], counts)
            hist[:] += np.bincount(cell * AMOUNTS + step, minlength=bins)
            yield from taxi._shard_frames(roaring.payload_from_rows, cfg, shard, SHARD_WIDTH, rows, amount)

    stats = loader.stream_frames(base, index, frames(), pipeline=2, timeout=300)
    return {"hist": hist, "shards": list(mine), "stats": stats}


class Reference(taxi.Reference):
    """``taxi.Reference`` over a table with the amount as its last axis.
    Its row algebra, filters and calls work on any number of axes; a
    condition adds a mask along the last one."""

    def __init__(self, cfg: dict, states: list[dict]):
        self.sets, counts, self.int_field = taxi._fields(cfg)
        self.shape = tuple(counts) + (AMOUNTS,)
        self._axis = {f: k for k, f in enumerate(self.sets)}
        self._amounts = np.arange(AMOUNT_MIN, AMOUNT_MIN + AMOUNTS, dtype=np.int64)
        self._count = sum(s["hist"] for s in states).reshape(self.shape)
        self._total = self._count * self._amounts
        self._summed: dict[tuple, np.ndarray] = {}

    def mask(self, call) -> np.ndarray:
        if call.name == "Row" and call.cond is not None:
            return self._condition(call.cond)
        return super().mask(call)

    def _condition(self, cond) -> np.ndarray:
        if cond.field != self.int_field:
            raise ValueError(f"reference: {cond.field!r} is not the int field")
        if cond.op == "between":
            lo_op, lo, hi_op, hi = cond.value
            if lo_op not in ("<", "<=") or hi_op not in ("<", "<="):
                raise ValueError(f"reference: no rule for {lo} {lo_op} f {hi_op} {hi}")
            met = _OPS[lo_op](_near(lo), self._amounts) & _OPS[hi_op](self._amounts, _near(hi))
        else:
            met = _OPS[cond.op](self._amounts, _near(cond.value))
        return met.reshape([1] * len(self.sets) + [AMOUNTS])

    def _by(self, fields: list[str], mask, table) -> np.ndarray:
        """As ``taxi``'s, over the table summed first along every axis that
        neither the mask nor ``fields`` tells apart: a query then touches
        a few thousand numbers and not the table's eight million. One
        summed table per (table, axes), kept."""
        keep = {self._axis[f] for f in fields} | {a for a, n in enumerate(mask.shape) if n > 1}
        drop = tuple(a for a in range(table.ndim) if a not in keep)
        key = (id(table), drop)
        if key not in self._summed:
            self._summed[key] = table.sum(axis=drop, keepdims=True)
        return super()._by(fields, mask, self._summed[key])


def _near(v: int) -> int:
    return max(-_FAR, min(_FAR, int(v)))
