"""Model step, prefill: host-clock milliseconds per batched prefill dispatch
in the window (``stats["prefill_s"] / stats["prefill_dispatches"]``; each
ends in the logits' copy to the host, which syncs)."""


def read(ctx):
    n = ctx.rec.delta("prefill_dispatches")
    return 1e3 * ctx.rec.delta("prefill_s") / n if n else None
