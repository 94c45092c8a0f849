"""Share of the slice's device program launches that the program did not
name, %: from the ``modules`` table of the run's host_spans reduction
(harness/host_spans.py, every device plane's ``XLA Modules`` line). The
program names each jitted program of the serving path ``pilosa_<call
type>`` (``named_jit``), so a module under any other name (``jit__pad``,
``jit_dynamic_slice``, ...) is an eager ``jnp`` operation on a concrete
array: a device program of its own, launched from Python beside the
query's program.

params: prefix   what the program's own modules start with

Launches and none of them eager gives 0.0; no trace, or no launch in the
slice, gives nothing."""

from . import host_spans


def read(params: dict, ctx: dict):
    red = host_spans.reduction(ctx)
    if not red:
        return None
    launches = sum(m["launches"] for m in red["modules"].values())
    if not launches:
        return None
    eager = sum(m["launches"] for name, m in red["modules"].items()
                if not name.startswith(params["prefix"]))
    return eager / launches * 100.0
