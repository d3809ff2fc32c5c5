"""Share of the lanes' page extent that the decode steps in the traced
window read: sum of the ``engine.decode`` spans' ``kv_pages`` over the
sum of ``n_slots * pages_per_seq`` (%). A decode that gathers every
lane's whole extent reads 100%; the paged kernel reads each active
lane's live pages. None where the spans carry no ``kv_pages``."""
import functools
import math
from pathlib import Path

from lib.trace import DEVICE_PLANE, SPAN_PREFIX, find_xplane

DECODE = "engine.decode"


@functools.lru_cache(maxsize=4)
def _pages_read(path: str, mtime_ns: int):
    """kv_pages of every decode span on the harness's thread inside the
    window its ``bench.*`` spans bound, from the trace at ``path``."""
    import jax
    profile = jax.profiler.ProfileData.from_file(path)
    bounds, pages = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            own, spans = [], []
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    own.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif ev.name == DECODE:
                    stats = dict(ev.stats)
                    if "kv_pages" in stats:
                        spans.append((ev.start_ns, int(stats["kv_pages"])))
            if own:                         # the harness's thread
                bounds.extend(own)
                pages.extend(spans)
    if not bounds:
        return []
    lo = min(s for s, _ in bounds)
    hi = max(e for _, e in bounds)
    return [n for t, n in pages if lo <= t <= hi]


def read(run):
    if run.trace is None:
        return None
    from lib import harness
    path = find_xplane(harness.TRACE_DIR)
    if path is None:
        return None
    read_ = _pages_read(str(path), Path(path).stat().st_mtime_ns)
    extents = {e["n_slots"] * math.ceil(e["s_max"] / min(e["page_size"],
                                                         e["s_max"]))
               for e in run.cell.layout["engines"]}
    if not read_ or len(extents) != 1:
        return None
    return sum(read_) / (len(read_) * extents.pop()) * 100
