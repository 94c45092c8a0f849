"""Mean device time of one launch of the programs whose name starts with
``prefix``, ms: from the ``XLA Modules`` line of the device planes in the
run's profiler trace (harness/host_spans.py; the reduction is shared with
the host_spans reader). The program names its jitted programs by call
type (``jit_pilosa_topn``, ``jit_pilosa_topn_filtered``, ...), so a prefix
is a call type.

params: prefix

No launch of such a program in the slice gives nothing, never 0."""

from . import host_spans


def read(params: dict, ctx: dict):
    red = host_spans.reduction(ctx)
    if not red:
        return None
    mine = [m for name, m in red["modules"].items() if name.startswith(params["prefix"])]
    launches = sum(m["launches"] for m in mine)
    return sum(m["total_s"] for m in mine) / launches * 1e3 if launches else None
