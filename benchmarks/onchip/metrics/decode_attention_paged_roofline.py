"""decode_attention_paged_roofline (%): the paged flash-decode kernel's
roofline share.  Least bytes (the live tokens' K/V once, q in, o out)
and FLOPs of every kernel call in the traced window (one per layer per
decode step; the grouped-query count of the cell's architecture module,
``paged_attention``) at peak, over the device time of the events named
``decode_attention_paged`` in the trace."""
from onchip import trace_reduce, work


def read(rec):
    if rec.trace is None or not rec.decode_ctx:
        return None
    t = trace_reduce.time_by_name(
        rec.trace.ops.get(rec.device, []), *rec.window_ns,
        ).get("decode_attention_paged", 0)
    if t <= 0:
        return None
    least = rec.spec.layers * sum(
        work.roofline_s(*rec.arch.paged_attention(rec.spec, ctx), rec.peak)
        for ctx in rec.decode_ctx if ctx)
    return 100.0 * least / (t / 1e9)
