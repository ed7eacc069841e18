"""Share of the traced serving window in which no operation ran on the
device, averaged over the chips the cell uses."""


def read(ctx):
    if ctx.get("kind") != "serve" or "summary" not in ctx:
        return None
    s = ctx["summary"]
    if not s["devices"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
