"""Model step, decode: host-clock milliseconds per decode dispatch in the
window (``stats["decode_s"] / stats["decode_steps"]``; each ends in the
logits' copy to the host, which syncs)."""


def read(ctx):
    n = ctx.rec.delta("decode_steps")
    return 1e3 * ctx.rec.delta("decode_s") / n if n else None
