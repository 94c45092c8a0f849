"""Share of the traced slice in which no operation ran on the device:
1 - (union of device-op intervals / slice length), in %. The slice is
timed by the process that holds the chip, around start_trace/stop_trace."""


def read(params: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or tr.get("busy_s") is None or not tr.get("window_s"):
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
