"""Wall seconds of one set-up phase, as run.py timed it.

params: phase ("load_s", "warm_s", "boot_s")."""


def read(params: dict, ctx: dict):
    return ctx["phases"].get(params["phase"])
