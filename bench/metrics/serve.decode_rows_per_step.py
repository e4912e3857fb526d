"""Rows in a decode batch: tokens decoded over engine steps that decoded,
in the window, from the engine's ``decoded_tokens`` counter."""


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx.get("decode_steps"):
        return None
    return ctx["decode_rows"] / ctx["decode_steps"]
