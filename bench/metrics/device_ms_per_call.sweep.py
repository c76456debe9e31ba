"""Device-busy time inside each ``evaluate_batch`` call span, per call."""

CALL_SPAN = "bench:sweep.evaluate_batch"


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.spans_named(CALL_SPAN)
    if not calls:
        return None
    busy = sum(run.trace.busy_within(a, b) for a, b in calls)
    return 1e3 * busy / len(calls)
