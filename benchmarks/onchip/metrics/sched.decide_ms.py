"""sched.decide_ms (ms): host time per scheduling decision in the window,
all time over all calls of the policy's ``decide`` (the harness's span
around it)."""


def read(rec):
    if not rec.decide_s:
        return None
    return 1e3 * sum(rec.decide_s) / len(rec.decide_s)
