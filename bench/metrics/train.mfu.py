"""Model FLOP/s utilisation of training: forward and backward FLOPs per
token (from shapes, recomputation not counted) times tokens per second
per chip, over the chip's bf16 peak, in percent."""


def read(ctx):
    if ctx.get("kind") != "train" or not ctx.get("peaks"):
        return None
    return (100.0 * ctx["tokens_per_s_per_chip"] * ctx["flops_per_token"]
            / ctx["peaks"]["bf16_flops"])
