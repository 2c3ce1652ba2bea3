"""KV cache: KV blocks held by slots, as a share of the pool, averaged over
the window's decode steps."""


def read(ctx):
    held = [h for _t, _ctx, h in ctx.rec.decode_log]
    if not held or not ctx.pool_blocks:
        return None
    return 100.0 * sum(held) / len(held) / ctx.pool_blocks
