"""95th percentile of the gaps between consecutive tokens of a request in
the traced window, on the host clock after each engine step.  Above the
knee every decode row waits for the step that carries a prefill, so the
tail sets those steps apart from the decode-only ones; it is a per-layer
reading there, as a tail of a saturated server is."""


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    return ctx.get("itl_p95_ms")
