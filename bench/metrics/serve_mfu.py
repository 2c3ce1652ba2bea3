"""Whole step: the FLOPs the window's prefill and decode work needs (counted
from shapes by ``bench.flops``), over the window's seconds and the bf16 peak
of the cell's chips (a share per chip: the work is shared by ``chips``)."""
from bench import flops


def read(ctx):
    rec, s = ctx.rec, ctx.sizes
    work = sum(flops.prefill_flops(s, rows) for _t, rows in rec.prefill_log)
    work += sum(flops.decode_flops(s, c) for _t, c, _h in rec.decode_log)
    if not work:
        return None
    return 100.0 * work / rec.window_s / (ctx.chips
                                          * ctx.peaks["bf16_flops"])
