"""Serving's model FLOP/s utilisation: the FLOPs the model needs for the
prefill and decode tokens the engine processed in the window (from
shapes; active experts only, causal attention), over the summed wall time
of the engine's steps, over the chip's bf16 peak, in percent.  At a fixed
offered rate this is a step's share of the peak, not the load."""


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx.get("peaks") \
            or not ctx.get("step_s"):
        return None
    return (100.0 * ctx["step_flops"] / ctx["step_s"]
            / ctx["peaks"]["bf16_flops"])
