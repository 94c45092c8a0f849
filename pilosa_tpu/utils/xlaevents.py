"""XLA compile events → /metrics, with the site that paid for them.

JAX reports every trace, lowering, backend compile and persistent-cache
lookup through ``jax.monitoring`` on the thread that asked for it. One
listener per kind, registered once per process, forwards them to the
server's StatsClient (module-level sink like saturation.set_stats: the
events fire deep inside jax where no client is in scope):

- ``xla_compile_seconds{site, program}``: one observation per backend
  compile REQUEST (compiled, or read from the persistent cache);
  ``site`` is the innermost span open on the compiling thread
  (``readback.join``, ``executor.TopN``, ...; ``none`` outside any),
  ``program`` the jitted function's name (``pilosa_topn`` — see
  executor/compile.py ``named_jit`` — or jax's own for eager ops);
- ``xla_lower_seconds{site}``: jaxpr trace + lowering to MLIR, the
  host's share of a compile that a cache hit does not save;
- ``xla_cache_lookups{result}``: persistent compilation cache ``hit`` /
  ``miss`` (a miss counts when the compiled program was written back).

Label cardinality is bounded by the code: span names and program names
are literals, never data.
"""

from __future__ import annotations

from pilosa_tpu.utils.tracing import GLOBAL_TRACER

_COMPILE = "/jax/core/compile/backend_compile_duration"
_LOWER = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_CACHE = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

_stats = None
_registered = False


def _on_duration(event: str, seconds: float, **kw) -> None:
    stats = _stats
    if stats is None:
        return
    if event == _COMPILE:
        program = str(kw.get("fun_name", "?"))
        if program.startswith("jit(") and program.endswith(")"):
            program = program[4:-1]
        stats.timing(
            "xla_compile_seconds",
            seconds,
            tags={"site": GLOBAL_TRACER.current_name() or "none", "program": program},
        )
    elif event in _LOWER:
        stats.timing(
            "xla_lower_seconds",
            seconds,
            tags={"site": GLOBAL_TRACER.current_name() or "none"},
        )


def _on_event(event: str, **kw) -> None:
    stats = _stats
    result = _CACHE.get(event)
    if stats is not None and result is not None:
        stats.count("xla_cache_lookups", tags={"result": result})


def set_stats(client) -> None:
    """Point the listeners at ``client`` (None silences them). jax has no
    public way to take a listener back, so they register on the first
    call and stay for the life of the process."""
    global _stats, _registered
    _stats = client
    if client is not None and not _registered:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _registered = True
