"""Mean time from a prefill's first token (the end of its
``engine.sample``) to the end of the ``cluster.step`` that holds it,
when the client gets the token (ms)."""
import numpy as np

from lib import phases


def read(run):
    p = phases.for_run(run)
    if p is None or not p.holds_ns:
        return None
    return float(np.mean(p.holds_ns)) * 1e-6
