"""Kernels: the fused paged-attention decode kernel's share of its roofline
in the traced part of the window.  The least time the chip could take for
each traced decode step's attention (the larger of its needed FLOPs over the
bf16 peak and its needed bytes over the HBM peak) over the kernel's device
time in the trace.  On several chips the trace's op time is a mean per
device, so the needed FLOPs and bytes are each chip's share of them."""
from bench import flops, trace

KERNEL = r"^paged_attention_kernel(\.\d+)?$"


def read(ctx):
    if ctx.trace is None:
        return None
    t0, t1 = ctx.rec.trace_t
    need = 0.0
    for t, c, _h in ctx.rec.decode_log:
        if t0 <= t <= t1:
            f, b = flops.paged_attention_work(ctx.sizes, c, ctx.kv_itemsize)
            need += max(f / ctx.chips / ctx.peaks["bf16_flops"],
                        b / ctx.chips / ctx.peaks["hbm_bytes_per_s"])
    spent = trace.op_seconds(ctx.trace, KERNEL)
    if not need or not spent:
        return None
    return 100.0 * need / spent
