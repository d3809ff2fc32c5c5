"""Device-idle share of the traced window under every other program span
(admission, compaction, decode dispatch, the cluster's step) (%)."""
from lib import phases


def read(run):
    p = phases.for_run(run)
    if p is None:
        return None
    return p.idle_pct({n for n in p.idle_ns
                       if n != phases.NO_SPAN and n not in phases.SAMPLE})
