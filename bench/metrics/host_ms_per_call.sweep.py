"""Wall time of each ``evaluate_batch`` call span that no device operation
overlaps: host preparation, dispatch and the signature writeback."""

CALL_SPAN = "bench:sweep.evaluate_batch"


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.spans_named(CALL_SPAN)
    if not calls:
        return None
    wall = sum(b - a for a, b in calls) * 1e-9
    busy = sum(run.trace.busy_within(a, b) for a, b in calls)
    return 1e3 * (wall - busy) / len(calls)
