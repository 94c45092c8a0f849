"""The benchmark's own reader of the PQL subset its traffic uses.

The references answer a query from its TEXT, so that whatever a traffic
file can draw, the reference can answer, and nothing of the program's
parser is in the comparison. Grammar:

    call  := NAME '(' [arg (',' arg)*] ')'
    arg   := call | NAME '=' (INT | NAME | call)
           | NAME OP INT | INT OP NAME OP INT | NAME | INT
    OP    := '>' '<' '>=' '<=' '==' '!='
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_TOKEN = re.compile(r"\s*(?:(\d+)|([A-Za-z_][A-Za-z0-9_\-]*)|(<=|>=|==|!=|[()=,<>]))")


class PQLError(ValueError):
    pass


@dataclass
class Cond:
    """``field op value`` or ``lo <= field <= hi`` (``op == 'between'``,
    ``value == (lo_op, lo, hi_op, hi)``)."""

    field: str
    op: str
    value: object


@dataclass
class Call:
    name: str
    children: list = field(default_factory=list)  # positional Calls
    pos: list = field(default_factory=list)  # positional names / ints
    kw: dict = field(default_factory=dict)  # name -> int | str | Call
    cond: Cond | None = None


def _tokens(text: str) -> list:
    out, i = [], 0
    text = text.rstrip()
    while i < len(text):
        m = _TOKEN.match(text, i)
        if m is None:
            raise PQLError(f"bad character at {i} in {text!r}")
        num, name, sym = m.groups()
        out.append(int(num) if num is not None else (name or sym))
        i = m.end()
    return out


_OPS = ("<", ">", "<=", ">=", "==", "!=")


def parse(text: str) -> Call:
    toks = _tokens(text)
    call, i = _call(toks, 0)
    if i != len(toks):
        raise PQLError(f"trailing input in {text!r}")
    return call


def _call(toks: list, i: int):
    name = toks[i]
    if not isinstance(name, str) or toks[i + 1] != "(":
        raise PQLError(f"expected a call at token {i}")
    call = Call(name)
    i += 2
    while toks[i] != ")":
        i = _arg(toks, i, call)
        if toks[i] == ",":
            i += 1
    return call, i + 1


def _arg(toks: list, i: int, call: Call) -> int:
    t, nxt = toks[i], toks[i + 1]
    if isinstance(t, int) and nxt in _OPS:  # lo <= field <= hi
        lo, lo_op, fld, hi_op, hi = toks[i : i + 5]
        call.cond = Cond(fld, "between", (lo_op, lo, hi_op, hi))
        return i + 5
    if isinstance(t, int):
        call.pos.append(t)
        return i + 1
    if nxt == "(":
        child, i = _call(toks, i)
        call.children.append(child)
        return i
    if nxt == "=":
        if toks[i + 3 : i + 4] == ["("]:
            call.kw[t], i = _call(toks, i + 2)
            return i
        call.kw[t] = toks[i + 2]
        return i + 3
    if nxt in _OPS:
        call.cond = Cond(t, nxt, toks[i + 2])
        return i + 3
    call.pos.append(t)
    return i + 1
