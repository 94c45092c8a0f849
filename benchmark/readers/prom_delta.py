"""Generic reader: the change of /metrics families between two scrapes.

params:
  stat    "mean"  d(<family>_sum) / d(<family>_count)
          "sum"   d(<family>)
          "ratio" d(<family>) / sum of d(<of[i].family>)
  family  the family (without the pilosa_tpu_ prefix), ``labels`` an
          optional substring its label string must hold
  of      for "ratio": [{"family", "labels"}] whose deltas are the base
  scale   multiplies the result (1000 for s -> ms, 100 for a share -> %)
  from, to  names of the scrapes; the window's start and end by default

Nothing to read (a base of 0) gives nothing.
"""


def _delta(ctx, params, family, labels=None):
    a = ctx["scrapes"][params.get("from", "window_start")]["metrics"].get(family, {})
    b = ctx["scrapes"][params.get("to", "window_end")]["metrics"].get(family, {})
    return sum(v - a.get(k, 0.0) for k, v in b.items() if not labels or labels in k)


def read(params: dict, ctx: dict):
    fam, labels, scale = params["family"], params.get("labels"), params.get("scale", 1.0)
    stat = params["stat"]
    if stat == "sum":
        return _delta(ctx, params, fam, labels) * scale
    if stat == "mean":
        n = _delta(ctx, params, fam + "_count", labels)
        return _delta(ctx, params, fam + "_sum", labels) / n * scale if n > 0 else None
    if stat == "ratio":
        base = sum(_delta(ctx, params, o["family"], o.get("labels")) for o in params["of"])
        return _delta(ctx, params, fam, labels) / base * scale if base > 0 else None
    raise ValueError(f"prom_delta: unknown stat {stat!r}")
