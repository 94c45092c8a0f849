"""The one general traffic generator: a traffic file in, query texts out.

A traffic file (``benchmark/traffic/<name>.json``) holds

- ``clients``: closed-loop connections, ``processes``: generator
  processes they are spread over;
- ``domains``: named parameter domains, each ``{"kind": ...}``:
    ``choice``    ``values``, drawn as text: uniform, or with ``"zipf": s``
                  the k-th value with weight ``1/(k+1)**s``;
    ``field_row`` ``fields`` {field: rows}: one ``field=row`` pair,
                  uniform over all rows of all fields;
  a domain with ``"compiled": true`` changes the program the server
  compiles (a structure or a constant), so set-up warms every value of
  it; the FIELD of a ``field_row`` always does, its row never;
- ``templates``: ``name``, ``share`` (whole numbers), ``pql`` with
  ``{placeholder}``s and ``params`` {placeholder: domain}.

Requests come in decks: every deck holds each template ``share`` times,
shuffled from the seed, so every seed and every client sends the same
mix in another order.
"""

from __future__ import annotations

import itertools
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        spec = json.load(f)
    for t in spec["templates"]:
        if int(t["share"]) != t["share"] or t["share"] <= 0:
            raise ValueError(f"template {t['name']}: share must be a whole number > 0")
    return spec


class Generator:
    def __init__(self, spec: dict, seed_words):
        self.spec = spec
        self.rng = np.random.default_rng(list(seed_words))
        self.templates = spec["templates"]
        self.domains = spec.get("domains", {})
        self._deck = np.repeat(
            np.arange(len(self.templates)), [t["share"] for t in self.templates]
        )
        self._left: list[int] = []
        self._zipf = {
            name: np.cumsum(1.0 / np.arange(1, len(d["values"]) + 1) ** d["zipf"])
            for name, d in self.domains.items()
            if d["kind"] == "choice" and "zipf" in d
        }

    # ------------------------------------------------------------- drawing
    def draw(self) -> tuple[int, str]:
        """(template index, query text) of the next request."""
        if not self._left:
            self._left = self.rng.permutation(self._deck).tolist()
        ti = self._left.pop()
        return ti, self.render(self.templates[ti], {})

    def render(self, template: dict, fixed: dict) -> str:
        """``fixed`` pins compiled choices by their placeholder (warm-up)."""
        values = {ph: self._value(dname, fixed.get(ph))
                  for ph, dname in template.get("params", {}).items()}
        return _fill(template["pql"], values)

    def _value(self, dname: str, fixed: int | None) -> str:
        d = self.domains[dname]
        kind = d["kind"]
        if kind == "choice":
            if fixed is not None:
                return str(d["values"][fixed])
            if "zipf" in d:
                cdf = self._zipf[dname]
                k = int(np.searchsorted(cdf, self.rng.random() * cdf[-1], side="right"))
                return str(d["values"][min(k, len(d["values"]) - 1)])
            return str(d["values"][int(self.rng.integers(len(d["values"])))])
        if kind == "field_row":
            names = list(d["fields"])
            if fixed is not None:
                fld = names[fixed]
            else:  # uniform over all rows of all fields
                sizes = np.cumsum([d["fields"][n] for n in names])
                fld = names[int(np.searchsorted(sizes, self.rng.integers(sizes[-1]), side="right"))]
            return f"{fld}={int(self.rng.integers(d['fields'][fld]))}"
        raise ValueError(f"domain {dname}: unknown kind {kind!r}")

    # ------------------------------------------------------------- warm-up
    def warmup(self) -> list[tuple[int, str]]:
        """One query of every compiled shape the mix can draw: each
        template times every combination of its compiled choices, the
        free parameters drawn from the seed."""
        out = []
        for ti, t in enumerate(self.templates):
            axes = []
            for ph, dname in t.get("params", {}).items():
                d = self.domains[dname]
                if d["kind"] == "field_row":
                    axes.append([(ph, k) for k in range(len(d["fields"]))])
                elif d.get("compiled"):
                    axes.append([(ph, k) for k in range(len(d["values"]))])
            out += [(ti, self.render(t, dict(combo))) for combo in itertools.product(*axes)]
        return out


def _fill(fmt: str, values: dict) -> str:
    out = fmt
    for k, v in values.items():
        out = out.replace("{" + k + "}", v)
    if "{" in out:
        raise ValueError(f"unfilled placeholder in {out!r}")
    return out
