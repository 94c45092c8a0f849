"""Sum of the numbers a pattern captures in the server's log between two
scrapes' marks. With JAX_LOG_COMPILES=1 every backend compile logs
"Finished XLA compilation of <name> in <seconds> sec"; the sum is the
time the window's requests waited on compiles (the tree has no compile
counter or timer of its own).

params: pattern (a regular expression with one group), scale; from, to
(scrape names, the window by default). No match gives nothing."""

import re


def read(params: dict, ctx: dict):
    a = ctx["scrapes"][params.get("from", "window_start")]["log_offset"]
    b = ctx["scrapes"][params.get("to", "window_end")]["log_offset"]
    with open(ctx["log_path"], "rb") as f:
        f.seek(a)
        text = f.read(max(0, b - a)).decode("utf-8", "replace")
    found = re.findall(params["pattern"], text)
    if not found:
        return None
    return sum(float(x) for x in found) * params.get("scale", 1.0)
