"""sched.queue_wait_ms (ms): p95 wait in the waiting queue, from a
request's entry (``submit``, its due time) to its first admission
(``admit``, when the policy staged its prefill), over the requests due in
the traced window: the wait for the tick in progress, for the policy's
decision and for a free slot or KV blocks.  The loop counts each wait on
the tick that admitted it (``StepGauge.queue_waits``); a request is
admitted at its tick's start (``admit`` is the gauge's ``t``), so its due
time is ``t - wait``, and waits due before the window's first tick are
left out.  The percentile is ``ttft_p95_ms``'s.  A request due in the
window but first admitted after it closes is not in the window's gauges
(at a load the cell sustains, one due in the window's last tick).  A
loop without the counter gives nothing."""
from onchip import traffic


def read(rec):
    if not rec.gauges:
        return None
    lo = min(g.t for g in rec.gauges)
    waits = [w for g in rec.gauges for w in getattr(g, "queue_waits", ())
             if g.t - w >= lo]
    return 1e3 * traffic.percentile(waits, 95) if waits else None
