"""CPU time of the generator processes over their wall time, %: near 100
means the generator, not the server, set the pace."""


def read(params: dict, ctx: dict):
    cpu = sum(p["cpu_s"] for p in ctx["loadgen"])
    wall = sum(p["wall_s"] for p in ctx["loadgen"])
    return cpu / wall * 100.0 if wall > 0 else None
