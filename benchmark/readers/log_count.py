"""Occurrences of a text in the server's log between two scrapes' marks
(byte offsets of the log taken with each scrape). The tree has no compile
counter; with JAX_LOG_COMPILES=1 every backend compile request, whether
compiled or read from the persistent cache, logs one line.

params: text; from, to (scrape names, the window by default)."""


def read(params: dict, ctx: dict):
    a = ctx["scrapes"][params.get("from", "window_start")]["log_offset"]
    b = ctx["scrapes"][params.get("to", "window_end")]["log_offset"]
    with open(ctx["log_path"], "rb") as f:
        f.seek(a)
        return float(f.read(max(0, b - a)).count(params["text"].encode()))
