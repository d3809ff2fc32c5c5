"""The device's idle time split over the program's own spans, and the
prefill spans' first-token hold and padding, from a profiler trace.

The serving step marks its layer boundaries with profiler spans named
``cluster.*`` and ``engine.*`` (docs/observability.md, "Profiler
spans"). This reads the same ``.xplane.pb`` as ``lib/trace.py``, over
the same window (the bounds of the harness's ``bench.*`` spans) and the
same device (the first TPU plane). Each interval in which that device
ran no operation is given to the innermost program span open at that
instant on the harness's thread; what no program span covers is the
harness's own loop. A trace without program spans reads as nothing.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from lib.trace import (DEVICE_PLANE, OPS_LINE, SPAN_PREFIX, Interval, clip,
                       find_xplane, gaps, merge)

PROGRAM_PREFIXES = ("cluster.", "engine.")
SAMPLE = ("engine.logits", "engine.sample")
NO_SPAN = ""


@dataclasses.dataclass
class Span:
    name: str
    start: float                    # ns
    end: float
    args: Dict[str, int]
    parent: Optional["Span"] = None
    children: List["Span"] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Phases:
    """What the readers take from one trace."""

    window_ns: float
    idle_ns: Dict[str, float]       # innermost program span -> idle ns
    holds_ns: List[float]           # per prefill: step end - sample end
    prompt_tokens: int              # summed over the window's prefills
    bucket_tokens: int

    def idle_pct(self, names) -> float:
        """Idle time under the program spans in ``names``, in percent
        of the window."""
        return sum(v for n, v in self.idle_ns.items() if n in names) \
            / self.window_ns * 100


def nest(spans: List[Span]) -> List[Span]:
    """Link the spans of one thread into their tree; returns them in
    start order. A child that outlasts its parent is cut at its end."""
    ordered = sorted(spans, key=lambda s: (s.start, -s.end))
    stack: List[Span] = []
    for s in ordered:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        if stack:
            s.end = min(s.end, stack[-1].end)
            s.parent = stack[-1]
            stack[-1].children.append(s)
        stack.append(s)
    return ordered


def innermost(ordered: List[Span]) -> List[Tuple[float, float, str]]:
    """Pieces of the time the nested spans cover, each named by the
    innermost span open there (``ordered`` as `nest` returns it)."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    t = float("-inf")

    def close(until: float) -> None:
        nonlocal t
        while stack and stack[-1].end <= until:
            s = stack.pop()
            if s.end > t:
                pieces.append((t, s.end, s.name))
                t = s.end

    for s in ordered:
        close(s.start)
        if stack and s.start > t:
            pieces.append((t, s.start, stack[-1].name))
        t = max(t, s.start)
        stack.append(s)
    close(float("inf"))
    return pieces


def attribute(idle: List[Interval], pieces: List[Tuple[float, float, str]]
              ) -> Dict[str, float]:
    """Idle ns per innermost span name (`NO_SPAN` for the rest); both
    lists sorted and disjoint."""
    out: Dict[str, float] = {}
    j = 0
    for s, e in idle:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b = max(s, pieces[k][0]), min(e, pieces[k][1])
            if b > a:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + b - a
                covered += b - a
            k += 1
        if e - s > covered:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + e - s - covered
    return out


def clip_pieces(pieces, lo: float, hi: float):
    return [(max(s, lo), min(e, hi), n) for s, e, n in pieces
            if e > lo and s < hi]


def _ancestor(s: Span, name: str) -> Optional[Span]:
    p = s.parent
    while p is not None and p.name != name:
        p = p.parent
    return p


def summarize(profile) -> Optional[Phases]:
    """Reduce a `jax.profiler.ProfileData`; None when it holds no device
    operation, no harness span or no program span."""
    bench: List[Interval] = []
    program: List[Span] = []
    ops: Dict[int, List[Interval]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                if line.name == OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        (float(ev.start_ns),
                         float(ev.start_ns + ev.duration_ns))
                        for ev in line.events)
                continue
            own: List[Interval] = []
            spans: List[Span] = []
            for ev in line.events:
                name = ev.name
                if name.startswith(SPAN_PREFIX):
                    own.append((float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns)))
                elif name.startswith(PROGRAM_PREFIXES):
                    spans.append(Span(
                        name, float(ev.start_ns),
                        float(ev.start_ns + ev.duration_ns),
                        dict(ev.stats) if name == "engine.prefill" else {}))
            if own:                         # the harness's thread
                bench.extend(own)
                program.extend(spans)
    if not ops or not bench or not program:
        return None
    lo = min(s for s, _ in bench)
    hi = max(e for _, e in bench)
    busy = clip(merge(ops[min(ops)]), lo, hi)
    ordered = nest(program)
    pieces = clip_pieces(innermost(ordered), lo, hi)
    holds, prompt, bucket = [], 0, 0
    for s in ordered:
        if s.name != "engine.prefill" or s.start < lo or s.end > hi:
            continue
        prompt += int(s.args.get("prompt_len", 0))
        bucket += int(s.args.get("bucket", 0))
        step = _ancestor(s, "cluster.step")
        sample = [c for c in s.children if c.name == "engine.sample"]
        if step is not None and sample and step.end <= hi:
            holds.append(step.end - sample[-1].end)
    return Phases(window_ns=hi - lo, idle_ns=attribute(gaps(busy, lo, hi),
                                                       pieces),
                  holds_ns=holds, prompt_tokens=prompt, bucket_tokens=bucket)


_CACHE: Dict[Tuple[str, int], Optional[Phases]] = {}


def load(path: Path) -> Optional[Phases]:
    """`summarize` of the trace file at ``path``, read once."""
    key = (str(path), Path(path).stat().st_mtime_ns)
    if key not in _CACHE:
        import jax
        _CACHE[key] = summarize(
            jax.profiler.ProfileData.from_file(str(path)))
    return _CACHE[key]


def for_run(run, trace_dir: Optional[Path] = None) -> Optional[Phases]:
    """The phases of a traced run's trace (the harness's trace
    directory unless ``trace_dir``); None for an untraced run."""
    if run.trace is None:
        return None
    if trace_dir is None:
        from lib import harness
        trace_dir = harness.TRACE_DIR
    path = find_xplane(trace_dir)
    return None if path is None else load(path)
