"""Share of the traced serving window in which no operation ran on the
device, in percent."""


def read(ctx):
    s = ctx.get("summary")
    if ctx.get("kind") != "serve" or not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
