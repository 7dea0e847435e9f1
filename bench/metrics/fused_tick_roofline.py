"""Share of its roofline the fused tick reached, in percent: the least time
the chip could take for the window's ticks (``cost.fused_tick``: the larger
of bytes over HBM bandwidth and operations over the float32
``Precision.HIGHEST`` compute roof, a sixth of the bf16 peak) over the
device time of the ``fused_tick`` programs in the trace."""
from isla_bench import cost, peaks


def read(ctx):
    if ctx.trace is None:
        return None
    device_s, _ = ctx.trace.module_seconds("fused_tick")
    if device_s <= 0:
        return None
    p = peaks.peaks(ctx.device_kind)
    flops_peak = peaks.f32_highest_flops(ctx.device_kind)
    least = 0.0
    for t in ctx.ticks:
        if t.samples <= 0 or not t.computed_keys:
            continue
        keys = [(ctx.group_domains[k[3]] if k[3] is not None else 1,
                 k[:3] if k[0] is not None else None, k[3])
                for k in t.computed_keys]
        flops, nbytes = cost.fused_tick(t.samples, keys, ctx.n_blocks)
        least += max(nbytes / p["hbm_bytes_per_s"], flops / flops_peak)
    return 100.0 * least / device_s
