"""loop.occupancy (slots): mean running slots per serving tick in the
window, from the loop's own per-tick ``StepGauge``."""


def read(rec):
    ticks = [g.active for g in rec.gauges]
    return sum(ticks) / len(ticks) if ticks else None
