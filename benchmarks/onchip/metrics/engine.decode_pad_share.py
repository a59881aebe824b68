"""engine.decode_pad_share (%): share of decode rows dispatched that
carried no running request, 1 - sum(running) / sum(dispatch width) over
the window's dispatching ticks (the loop's ``StepGauge``); the waste of
the pow-2 slot buckets."""


def read(rec):
    ticks = [g for g in rec.gauges if g.dispatch_width > 0]
    width = sum(g.dispatch_width for g in ticks)
    if not width:
        return None
    return 100.0 * (1.0 - sum(min(g.active, g.dispatch_width)
                              for g in ticks) / width)
