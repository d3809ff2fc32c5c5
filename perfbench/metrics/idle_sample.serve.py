"""Device-idle share of the traced window under the engine's logits copy
and sampling (``engine.logits``, ``engine.sample``) (%)."""
from lib import phases


def read(run):
    p = phases.for_run(run)
    return None if p is None else p.idle_pct(phases.SAMPLE)
