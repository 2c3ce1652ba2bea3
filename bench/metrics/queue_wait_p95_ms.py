"""Scheduler: 95th percentile, over the requests due in the window, of the
wait from due time to slot assignment (``Request.stats["queue_s"]``); one
still queued at the window's close counts with its wait so far."""
from bench.e2e import nearest_rank


def read(ctx):
    rec = ctx.rec
    waits = [r.stats["queue_s"] if "queue_s" in r.stats else rec.t1 - r.due
             for r in rec.due if r.status != "rejected"]
    return nearest_rank(waits, 95) * 1e3 if waits else None
