"""Host/device boundary rule.

The executor's whole latency story (docs/query-routing.md) rests on one
invariant: a query pays AT MOST ONE device→host sync, in the executor's
readback wave.  Any other code that forces a sync on a JAX value —
``np.asarray`` / ``np.array`` / ``float()`` / ``int()`` / ``.item()`` /
``.block_until_ready()`` / ``jax.device_get`` — re-introduces the ~70 ms
per-sync stall the cost router exists to avoid (PR 2), silently, from
anywhere.

Sanctioned readback layer: modules under ``executor/`` and
``parallel/`` (the readback wave, the compiler's host bridge, the mesh
gather paths) — EXCEPT ``executor/scheduler.py``: the cross-query wave
scheduler coordinates many requests' results, which is exactly where an
accidental early sync would silently serialize every wave, so only its
settlement function (``fetch_wave``, the one settlement a wave pays:
every result's copy started together, awaited once) is sanctioned,
explicitly by name rather than by the directory it lives in.
Everywhere else, in any module that imports jax:

- ``.block_until_ready()`` and ``jax.device_get(...)`` are flagged
  unconditionally (they have no host-side meaning);
- the host-coercion calls are flagged only when their argument visibly
  derives from a device value — a ``jnp.*`` / ``jax.*`` subexpression,
  or a local name assigned from one in the same function (a light
  intra-function taint; it will not catch laundering through
  containers, but it catches the way this mistake is actually made).

Transitive pass (call graph): a sync three helpers deep is still a
sync.  For every function OUTSIDE the sanctioned layer, the rule
computes whether it can reach a sync fact through a chain of other
outside-layer functions, and flags the CALL EDGE into any reaching
helper — so the caller is attributed, not just the terminal site.
Propagation stops at the layer boundary (a call into ``executor/`` or
``parallel/`` is the sanctioned hand-off, not a leak), and a sync fact
whose own line carries ``allow(readback)`` does not propagate — the
site pragma asserts the sync is safe in every context.  An
``allow(readback)`` pragma on a call line cuts that edge only.
"""

from __future__ import annotations

import ast

from tools.analysis.engine import Project, Violation, call_name, functions, rule

SANCTIONED_PREFIXES = ("pilosa_tpu/executor/", "pilosa_tpu/parallel/")
# the scheduler is carved OUT of the executor/ blanket: only the named
# settlement function may sync (see module docstring)
SCHEDULER_FILE = "executor/scheduler.py"
SCHEDULER_SANCTIONED_FUNCS = {"fetch_wave"}
_ALWAYS_SYNC = ("block_until_ready",)
_COERCE_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
_COERCE_BUILTINS = {"float", "int"}


def _is_device_expr(node: ast.AST, tainted: set[str]) -> bool:
    """Does this expression visibly involve a jax/jnp value?"""
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in tainted:
            return True
        if isinstance(n, ast.Attribute):
            root = n
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id in ("jnp", "jax"):
                return True
    return False


def _classify_sync(node: ast.Call, tainted: set[str]) -> str | None:
    """Short description when this call is a device→host sync, else
    None — the one classifier both the direct and transitive passes
    share."""
    name = call_name(node.func)
    short = name.rsplit(".", 1)[-1]
    if short in _ALWAYS_SYNC:
        return f"{short}()"
    if name == "jax.device_get":
        return "jax.device_get()"
    is_coerce = name in _COERCE_CALLS or (
        name in _COERCE_BUILTINS and len(node.args) == 1
    )
    if is_coerce and node.args and _is_device_expr(node.args[0], tainted):
        return f"{name or short}() on a JAX value"
    if short == "item" and not node.args and _is_device_expr(
        node.func, tainted
    ):
        return ".item() on a JAX value"
    return None


def _taint(fn: ast.AST) -> set[str]:
    """Local names assigned from jnp.* / jax.* calls."""
    tainted: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            name = call_name(node.value.func)
            if name.startswith(("jnp.", "jax.")):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        tainted.add(tgt.id)
                    elif isinstance(tgt, ast.Tuple):
                        tainted.update(
                            e.id for e in tgt.elts if isinstance(e, ast.Name)
                        )
    return tainted


def _is_scheduler(rel: str) -> bool:
    return rel == SCHEDULER_FILE or rel.endswith("/" + SCHEDULER_FILE)


def _in_layer(rel: str) -> bool:
    """Inside the sanctioned readback layer (ignoring the scheduler
    carve-out, which is per-function)."""
    return any(s in rel for s in SANCTIONED_PREFIXES) or any(
        rel.startswith(p.split("pilosa_tpu/")[1]) for p in SANCTIONED_PREFIXES
    )


def _outside_layer(info) -> bool:
    """True when a call-graph function is OUTSIDE the sanctioned layer
    — the scheduler's functions count as outside except ``fetch_wave``,
    the named settlement function."""
    if _is_scheduler(info.rel):
        return info.name not in SCHEDULER_SANCTIONED_FUNCS
    return not _in_layer(info.rel)


@rule(
    "readback",
    "device→host syncs outside the sanctioned readback layer (executor/, parallel/)",
)
def check_readback(project: Project) -> list[Violation]:
    out: list[Violation] = []
    for f in project.files:
        if f.tree is None:
            continue
        is_scheduler = _is_scheduler(f.rel)
        if not is_scheduler and _in_layer(f.rel):
            continue
        if not f.imports_module("jax", "jax.numpy"):
            continue
        # function scopes first (their own taint sets), then the module
        # scope for top-level code; the seen-set keeps nested nodes from
        # double-reporting when the module walk revisits function bodies
        scopes = list(functions(f.tree)) + [f.tree]
        seen: set[int] = set()
        for fn in scopes:
            if (
                is_scheduler
                and isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                and fn.name in SCHEDULER_SANCTIONED_FUNCS
            ):
                # the named settlement layer: its syncs ARE the wave's
                # one settlement. Mark its nodes seen so the module-scope
                # walk doesn't re-report them.
                seen.update(
                    id(n) for n in ast.walk(fn) if isinstance(n, ast.Call)
                )
                continue
            tainted = _taint(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                seen.add(id(node))
                desc = _classify_sync(node, tainted)
                if desc is not None:
                    out.append(
                        Violation(
                            "readback",
                            f.rel,
                            node.lineno,
                            f"{desc} forces a device sync outside the "
                            "readback layer — return the device value and "
                            "let the executor's readback wave fetch it",
                        )
                    )
    out.extend(_transitive(project))
    return out


def _transitive(project: Project) -> list[Violation]:
    """Flag call edges, in outside-layer functions, into outside-layer
    helpers that transitively reach a sync fact."""
    from tools.analysis.callgraph import _own_nodes, get_callgraph

    cg = get_callgraph(project)

    # own sync facts per outside-layer function (same file gate as the
    # direct pass: only jax-importing files can PRODUCE a fact; any
    # outside function can propagate one)
    jax_rels = {
        f.rel
        for f in project.files
        if f.tree is not None and f.imports_module("jax", "jax.numpy")
    }
    facts: dict[tuple[str, str], tuple[str, int]] = {}
    for info in cg.functions.values():
        if not _outside_layer(info):
            continue
        if info.rel not in jax_rels:
            continue
        f = project._by_rel.get(info.rel)
        if f is None:
            continue
        tainted = _taint(info.node)
        for node in _own_nodes(info.node):
            if not isinstance(node, ast.Call):
                continue
            desc = _classify_sync(node, tainted)
            if desc is None:
                continue
            if f.allowed("readback", node.lineno):
                # the site pragma asserts "safe in every context" — it
                # kills propagation too, and counts as used
                project.note_pragma_use(info.rel, node.lineno, "readback")
                continue
            facts.setdefault(info.key, (desc, node.lineno))

    # fixpoint: reaches[key] = witness (desc, rel, line) when the
    # function has a fact or any outside-layer callee reaches one
    reaches: dict[tuple[str, str], tuple[str, str, int]] = {
        k: (d, k[0], ln) for k, (d, ln) in facts.items()
    }
    edges: dict[tuple[str, str], list[tuple[object, int]]] = {}
    for info in cg.functions.values():
        if _outside_layer(info):
            edges[info.key] = [
                (t, ln)
                for t, ln in cg.callees(info, "readback")
                if _outside_layer(t)
            ]
    changed = True
    while changed:
        changed = False
        for key, outgoing in edges.items():
            if key in reaches:
                continue
            for target, _ln in outgoing:
                w = reaches.get(target.key)
                if w is not None:
                    reaches[key] = w
                    changed = True
                    break

    out: list[Violation] = []
    for key, outgoing in edges.items():
        caller = cg.functions[key]
        for target, line in outgoing:
            w = reaches.get(target.key)
            if w is None:
                continue
            desc, wrel, wline = w
            out.append(
                Violation(
                    "readback",
                    caller.rel,
                    line,
                    f"{caller.qualname}() calls {target.qualname}(), which "
                    f"transitively forces a device sync ({desc} at "
                    f"{wrel}:{wline}) outside the readback layer — route "
                    "the fetch through the executor, or pragma this edge",
                )
            )
    return out
