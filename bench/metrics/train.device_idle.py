"""Share of the traced training window in which no operation ran on the
device (averaged over the chips), in percent."""


def read(ctx):
    s = ctx.get("summary")
    if ctx.get("kind") != "train" or not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
