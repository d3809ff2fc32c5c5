"""90th percentile wait from submission to admission (the engine's
``t_admit - t_submit``) over requests admitted while the trace ran (ms).

Only the traced span is read: the profiler's start and stop hold the
harness's loop for seconds, and the backlog they leave waits for lanes
until the end of the window."""
from lib import stats


def read(run):
    if run.trace_span is None:
        return None
    lo, hi = run.trace_span
    waits = []
    for t in run.tracked:
        t_admit = getattr(t.req, "t_admit", 0.0)
        if not t_admit:
            continue
        wait = t_admit - t.req.t_submit
        if lo <= t.submitted + wait <= hi:
            waits.append(wait)
    v = stats.percentile(waits, 90)
    return None if v is None else v * 1e3
