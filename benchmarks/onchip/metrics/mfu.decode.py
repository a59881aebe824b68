"""mfu.decode (%): roofline share of the whole decode step.  The least
time of every decode step dispatched in the traced window (the larger of
its FLOPs over peak and its bytes over peak HBM bandwidth: weights once,
live K/V read, new K/V written), as the cell's architecture module
counts it (``decode_step``), over the device time of the decode program
(``_decode_dispatch_paged``) in the trace."""
from onchip import trace_reduce, work


def read(rec):
    if rec.trace is None or not rec.decode_ctx:
        return None
    t = sum(ns for name, ns in trace_reduce.time_by_name(
        rec.trace.modules.get(rec.device, []), *rec.window_ns,
        key=trace_reduce.module_name).items()
        if "decode_dispatch_paged" in name)
    if t <= 0:
        return None
    least = sum(work.roofline_s(*rec.arch.decode_step(rec.spec, ctx),
                              rec.peak)
                for ctx in rec.decode_ctx if ctx)
    return 100.0 * least / (t / 1e9)
