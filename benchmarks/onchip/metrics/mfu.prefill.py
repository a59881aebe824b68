"""mfu.prefill (%): roofline share of whole-prompt prefill.  The least
time of every prefill in the traced window, counting unpadded prompt
tokens only, as the cell's architecture module counts it (``prefill``),
over the device time of the prefill program (``_prefill_paged``) in the
trace; bucket padding shows as waste."""
from onchip import trace_reduce, work


def read(rec):
    if rec.trace is None or not rec.prefill_n:
        return None
    t = sum(ns for name, ns in trace_reduce.time_by_name(
        rec.trace.modules.get(rec.device, []), *rec.window_ns,
        key=trace_reduce.module_name).items()
        if "prefill_paged" in name)
    if t <= 0:
        return None
    least = sum(work.roofline_s(*rec.arch.prefill(rec.spec, n), rec.peak)
                for n in rec.prefill_n if n > 0)
    return 100.0 * least / (t / 1e9)
