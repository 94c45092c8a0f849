"""Dataset ``taxi``: upstream Pilosa's "Transportation" example.

The configuration's file names the fields: set fields, each with its
rows and the share of columns each row holds (in 256ths), and one BSI
int field. Every column carries one value of each field, drawn from the
seed; the value distributions are assumed, not the trip data.

The reference is one joint table ``[row of set field 1, ..., row of set
field k] -> (columns, sum of the int field)``, accumulated while the
shards are generated. Every query the traffic draws (set algebra over the
set fields, ``Count``, ``TopN``, ``Sum``, ``GroupBy``) is a sum over
cells of it, so the reference answers from the query TEXT with nothing
of the program's parser, planner, engines or ops in it. It has no rule
for a range over the int field or for ``Min``/``Max``: a traffic file
that draws one fails its run loudly.
"""

from __future__ import annotations

import json

import numpy as np

BSI_EXISTS, BSI_OFFSET = 0, 2  # bsi view rows: 0 exists, 1 sign, 2.. bits


def _fields(cfg: dict) -> tuple[list[str], list[int], str]:
    """(set field names, their row counts, the int field's name), in the
    configuration's order. One byte of a seeded draw picks a set field's
    row, so at most four set fields."""
    sets = [f for f, s in cfg["schema"].items() if s["type"] == "set"]
    (int_field,) = [f for f, s in cfg["schema"].items() if s["type"] == "int"]
    if len(sets) > 4:
        raise ValueError("dataset taxi: at most four set fields")
    return sets, [cfg["schema"][f]["rows"] for f in sets], int_field


def _luts(cfg: dict) -> list[np.ndarray]:
    out = []
    for f in _fields(cfg)[0]:
        shares = cfg["schema"][f]["shares_of_256"]
        if len(shares) != cfg["schema"][f]["rows"] or sum(shares) != 256:
            raise ValueError(f"dataset taxi: {f}: one share a row, 256 in all")
        out.append(np.repeat(np.arange(len(shares), dtype=np.uint8), shares))
    return out


def schema(cfg: dict) -> list[tuple[str, bytes]]:
    out = []
    for f, s in cfg["schema"].items():
        opts = {"options": {"type": "int", "min": s["min"], "max": s["max"]}} if s["type"] == "int" else {}
        out.append((f, json.dumps(opts).encode()))
    return out


def parts(cfg: dict) -> int:
    """Independent pieces the load can be split into."""
    return int(cfg["scale"]["shards"])


def gen_shard(seed: int, shard: int, width: int, luts: list[np.ndarray]):
    """The seeded columns of one shard: ([row of each set field] uint8,
    amount int64). Two seeded 32-bit draws per column."""
    rng = np.random.default_rng([seed, shard])
    x = rng.integers(0, 1 << 32, width, dtype=np.uint32)
    y = rng.integers(0, 1 << 32, width, dtype=np.uint32).astype(np.int64)
    rows = [lut[(x >> (8 * k)) & 0xFF] for k, lut in enumerate(luts)]
    v = ((y & 0xFFFF) * (y >> 16)) >> 16  # skewed low
    return rows, 3 + ((v * v) >> 22)


def _pack(mask: np.ndarray) -> np.ndarray:
    return np.packbits(mask, bitorder="little").view(np.uint32)


def _shard_frames(payload_from_rows, cfg: dict, shard: int, width: int, rows, amount):
    sets, counts, int_field = _fields(cfg)
    for f, n, col in zip(sets, counts, rows):
        yield (f, "standard", shard) + payload_from_rows((r, _pack(col == r)) for r in range(n))
    slices = [(BSI_EXISTS, _pack(np.ones(width, dtype=bool)))]
    for b in range(cfg["schema"][int_field]["bits"]):
        bit = ((amount >> b) & 1).astype(bool)
        if bit.any():  # an empty slice is no row
            slices.append((BSI_OFFSET + b, _pack(bit)))
    yield (int_field, "bsi", shard) + payload_from_rows(slices)


def load_part(base: str, index: str, seed: int, cfg: dict, mine: list[int]) -> dict:
    """Generate the shards in ``mine`` and post them over the program's
    bulk route with its own client (``loader.stream_frames``); returns
    this piece of the reference's state."""
    from pilosa_tpu import loader, roaring
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    luts = _luts(cfg)
    counts = _fields(cfg)[1]
    cells = int(np.prod(counts))
    count = np.zeros(cells, dtype=np.int64)
    total = np.zeros(cells, dtype=np.int64)

    def frames():
        for shard in mine:
            rows, amount = gen_shard(seed, shard, SHARD_WIDTH, luts)
            cell = np.ravel_multi_index([r.astype(np.intp) for r in rows], counts)
            count[:] += np.bincount(cell, minlength=cells)
            # float64 holds a shard's sums exactly: under 2**53
            total[:] += np.bincount(cell, weights=amount, minlength=cells).astype(np.int64)
            yield from _shard_frames(roaring.payload_from_rows, cfg, shard, SHARD_WIDTH, rows, amount)

    stats = loader.stream_frames(base, index, frames(), pipeline=2, timeout=300)
    return {"count": count, "total": total, "shards": list(mine), "stats": stats}


def drop_last_part(states: list[dict]) -> list[dict]:
    """The control: the same reference with one part's columns missing,
    as a server that lost acknowledged imports would answer."""
    last = max(states, key=lambda s: max(s["shards"], default=-1))
    return [s for s in states if s is not last]


# --------------------------------------------------------------- reference
class Reference:
    def __init__(self, cfg: dict, states: list[dict]):
        self.sets, counts, self.int_field = _fields(cfg)
        self.shape = tuple(counts)
        self._axis = {f: k for k, f in enumerate(self.sets)}
        self._count = sum(s["count"] for s in states).reshape(self.shape)
        self._total = sum(s["total"] for s in states).reshape(self.shape)

    # ---------------------------------------------------------- row algebra
    def mask(self, call) -> np.ndarray:
        """bool, broadcastable to the table: the cells whose columns are
        in the row the call describes."""
        name = call.name
        if name == "Row":
            if call.cond is not None:
                raise ValueError("reference: no rule for a range over the int field")
            ((fld, row),) = call.kw.items()
            shape = [1] * len(self.shape)
            shape[self._axis[fld]] = self.shape[self._axis[fld]]
            return (np.arange(self.shape[self._axis[fld]]) == row).reshape(shape)
        kids = [self.mask(c) for c in call.children]
        if name == "Intersect":
            return _fold(np.logical_and, kids)
        if name == "Union":
            return _fold(np.logical_or, kids)
        if name == "Xor":
            return _fold(np.logical_xor, kids)
        if name == "Difference":
            return _fold(lambda a, b: a & ~b, kids)
        if name == "Not":  # every column exists
            return ~kids[0]
        raise ValueError(f"reference: no row call {name!r}")

    def _filter(self, call) -> np.ndarray:
        """The call's filter: its first positional row call, or
        ``filter=``; none means every column."""
        f = call.children[0] if call.children and call.name != "GroupBy" else call.kw.get("filter")
        return self.mask(f) if f is not None else np.ones([1] * len(self.shape), dtype=bool)

    def _by(self, fields: list[str], mask, table) -> np.ndarray:
        """The table's totals over the masked cells, by the rows of
        ``fields`` (axes in the order of ``fields``)."""
        kept = np.where(mask, table, 0)
        axes = [self._axis[f] for f in fields]
        out = kept.sum(axis=tuple(a for a in range(len(self.shape)) if a not in axes))
        return np.transpose(out, np.argsort(np.argsort(axes))) if len(axes) > 1 else out

    # ---------------------------------------------------------------- calls
    def answer(self, call):
        """What ``results[0]`` of the program's JSON reply must equal."""
        name = call.name
        if name == "Count":
            return int(self._by([], self.mask(call.children[0]), self._count))
        if name == "Sum":
            if call.kw["field"] != self.int_field:
                raise ValueError(f"reference: {call.kw['field']!r} is not the int field")
            m = self._filter(call)
            return {"value": int(self._by([], m, self._total)), "count": int(self._by([], m, self._count))}
        if name == "TopN":
            counts = self._by([call.pos[0]], self._filter(call), self._count)
            order = sorted(range(counts.size), key=lambda r: (-int(counts[r]), r))
            pairs = [{"id": r, "count": int(counts[r])} for r in order if counts[r] > 0]
            n = call.kw.get("n")
            return pairs[:n] if n else pairs
        if name == "GroupBy":
            return self._groupby(call)
        raise ValueError(f"reference: no call {name!r}")

    def _groupby(self, call) -> list[dict]:
        fields = [c.pos[0] for c in call.children if c.name == "Rows"]
        mask = self._filter(call)
        counts = self._by(fields, mask, self._count)
        sums = self._by(fields, mask, self._total) if "aggregate" in call.kw else None
        out = []
        for rows in np.argwhere(counts > 0).tolist():  # row-major: nested ascending
            g = {"group": [{"field": f, "rowID": r} for f, r in zip(fields, rows)],
                 "count": int(counts[tuple(rows)])}
            if sums is not None:
                g["sum"] = int(sums[tuple(rows)])
            out.append(g)
        return out


def _fold(op, kids):
    out = kids[0]
    for k in kids[1:]:
        out = op(out, k)
    return out
