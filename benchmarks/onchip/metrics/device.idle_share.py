"""device.idle_share (%): share of the traced window in which no
operation ran on the device, 1 - union(op intervals) / window."""
from onchip import trace_reduce


def read(rec):
    if rec.trace is None or not rec.trace.ops.get(rec.device):
        return None
    lo, hi = rec.window_ns
    busy = trace_reduce.total(trace_reduce.busy(
        rec.trace.ops.get(rec.device, []), lo, hi))
    return 100.0 * (1.0 - busy / (hi - lo)) if hi > lo else None
