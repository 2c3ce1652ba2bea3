"""Scheduler: decode rows that carried a request's token, as a share of the
rows every decode step in the window dispatched (steps x max_batch)."""


def read(ctx):
    steps = ctx.rec.delta("decode_steps")
    if not steps:
        return None
    return 100.0 * ctx.rec.delta("decode_tokens") / (steps * ctx.max_batch)
