"""loop.host_ms (ms): host time per serving tick that dispatched a decode
round, meaned over the traced window's ticks: the tick (``serve.tick``)
less the time its sync spans (``serve.sync``, ``engine.sync``) spent
blocked on the device.  The loop times both around those spans and
keeps them on the tick's ``StepGauge`` (``tick_s``, ``sync_s``).  A loop
without them gives nothing."""


def read(rec):
    ticks = [g for g in rec.gauges
             if g.dispatch_width > 0 and hasattr(g, "tick_s")]
    if not ticks:
        return None
    return 1e3 * sum(g.tick_s - g.sync_s for g in ticks) / len(ticks)
