"""The device's idle time split over the program's spans
(``lib/phases.py``) and the readers built on it, on made-up traces and
on traces recorded on the chip."""
import dataclasses
import shutil
from typing import Dict, List

import pytest

from conftest import BENCH
from lib import harness, phases, trace

DATA = BENCH / "tests" / "data"
OLD_TRACE = DATA / "tiny_chip.xplane.pb"          # before program spans
SPAN_TRACE = DATA / "tiny_chip_spans.xplane.pb"
READERS = ("idle_sample.serve", "idle_schedule.serve",
           "first_token_hold_ms.serve", "prefill_pad_share.serve")


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    end: float
    stats: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def duration_ns(self):
        return self.end - self.start_ns


@dataclasses.dataclass
class Line:
    name: str
    events: List[Ev]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


@dataclasses.dataclass
class Profile:
    planes: List[Plane]


def made_up(program=True):
    """Two harness steps over 0..200 ns. The first runs a whole serving
    step with one admission; in the second the trace stopped inside the
    cluster's step, so only its prefill was recorded."""
    spans = [
        Ev("bench.step", 0, 100),
        Ev("cluster.step", 2, 98),
        Ev("cluster.commit_ready", 2, 4),
        Ev("engine.step", 5, 90),
        Ev("engine.admit", 5, 40),
        Ev("engine.prefill", 8, 38, {"prompt_len": 5, "bucket": 8}),
        Ev("engine.logits", 20, 30), Ev("engine.sample", 30, 36),
        Ev("engine.compact", 40, 42),
        Ev("engine.decode", 42, 50),
        Ev("engine.logits", 50, 80), Ev("engine.sample", 80, 88),
        Ev("cluster.handoff", 92, 96),
        Ev("bench.idle_wait", 100, 120),
        Ev("bench.step", 120, 200),
        Ev("engine.prefill", 125, 150, {"prompt_len": 11, "bucket": 16}),
        Ev("engine.logits", 140, 145), Ev("engine.sample", 145, 148),
    ]
    if not program:
        spans = [s for s in spans if s.name.startswith("bench.")]
    host = Plane("/host:CPU", [
        Line("main", spans),
        # another thread's spans are not the serving step's
        Line("worker", [Ev("engine.step", 0, 200)]),
        Line("python3", [Ev("$engine.py:960 step", 5, 90)])])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 10, 22),
        Ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b)", 45, 75),
        Ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 130, 140)])])
    return Profile([host, dev])


def test_idle_goes_to_the_innermost_span():
    p = phases.summarize(made_up())
    assert p.window_ns == 200
    # idle 0..10, 22..45, 75..130, 140..200
    assert p.idle_ns == {
        phases.NO_SPAN: 2 + 27 + 50, "cluster.commit_ready": 2,
        "cluster.step": 1 + 2 + 2, "engine.admit": 3 + 2,
        "engine.prefill": 2 + 2 + 5 + 2, "engine.logits": 8 + 5 + 5,
        "engine.sample": 6 + 8 + 3, "engine.compact": 2,
        "engine.decode": 3, "engine.step": 2, "cluster.handoff": 4}
    s = trace.summarize(made_up())
    assert s.mean_busy_s * 1e9 + sum(p.idle_ns.values()) \
        == pytest.approx(200)


def test_holds_and_padding_of_the_window_s_prefills():
    p = phases.summarize(made_up())
    # the second prefill's step was cut by the trace's stop
    assert p.holds_ns == [98 - 36]
    assert (p.prompt_tokens, p.bucket_tokens) == (5 + 11, 8 + 16)


def test_without_program_spans_nothing_is_read():
    assert phases.summarize(made_up(program=False)) is None
    p = made_up()
    p.planes = p.planes[:1]
    assert phases.summarize(p) is None


def test_nest_cuts_a_child_at_its_parent_s_end():
    a = phases.Span("cluster.step", 0, 10, {})
    b = phases.Span("engine.step", 2, 12, {})
    c = phases.Span("engine.step", 12, 14, {})
    ordered = phases.nest([c, b, a])
    assert [s.name for s in ordered] == ["cluster.step", "engine.step",
                                         "engine.step"]
    assert b.parent is a and b.end == 10 and c.parent is None
    assert phases.innermost(ordered) == [
        (0, 2, "cluster.step"), (2, 10, "engine.step"),
        (12, 14, "engine.step")]


class FakeRun:
    trace = object()


def read(name, run):
    return harness.read_metric(name, run)


def test_readers_on_a_made_up_trace(monkeypatch):
    p = phases.summarize(made_up())
    monkeypatch.setattr(phases, "for_run", lambda run: p)
    run = FakeRun()
    assert read("idle_sample.serve", run) == pytest.approx(
        (18 + 17) / 200 * 100)
    assert read("idle_schedule.serve", run) == pytest.approx(
        (2 + 5 + 5 + 11 + 2 + 3 + 2 + 4) / 200 * 100)
    assert read("first_token_hold_ms.serve", run) == pytest.approx(62e-6)
    assert read("prefill_pad_share.serve", run) == pytest.approx(
        (1 - 16 / 24) * 100)


@dataclasses.dataclass
class Req:
    t_submit: float
    t_admit: float = 0.0


@dataclasses.dataclass
class Tracked:
    req: Req
    submitted: float


def test_queue_wait_over_the_requests_admitted_while_tracing():
    run = FakeRun()
    run.trace_span = None
    assert read("queue_wait_p90_ms", run) is None
    run.trace_span = (100.0, 200.0)
    # serving-clock stamps 1000 s off the harness clock
    run.tracked = [Tracked(Req(1000 + t, 1000 + t + w), t)
                   for t, w in ((90.0, 5.0), (95.0, 20.0), (150.0, 0.002),
                                (160.0, 0.004), (199.0, 3.0))]
    run.tracked.append(Tracked(Req(1180.0), 180.0))     # not admitted
    got = read("queue_wait_p90_ms", run)
    want = sorted([0.002, 0.004, 20.0])
    assert got == pytest.approx(
        (want[1] + 0.8 * (want[2] - want[1])) * 1e3)
    # a program without the admission stamp reads nothing
    for t in run.tracked:
        t.req.t_admit = 0.0
    assert read("queue_wait_p90_ms", run) is None


def test_the_trace_before_program_spans_reads_nothing(tmp_path):
    """The recorded trace of a program without spans: every new reader
    returns None, as on the parent of the change that added them."""
    import jax
    old = jax.profiler.ProfileData.from_file(str(OLD_TRACE))
    assert phases.summarize(old) is None
    run = FakeRun()
    d = tmp_path / "plugins" / "profile" / "old"
    d.mkdir(parents=True)
    shutil.copy(OLD_TRACE, d / "old.xplane.pb")
    assert phases.for_run(run, tmp_path) is None
    run.trace = None
    assert phases.for_run(run, tmp_path) is None


def spans_of(profile):
    """The program's spans on the harness's thread, nested, and the
    harness's ``bench.step`` intervals."""
    program, steps = [], []
    for plane in profile.planes:
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns),
                    float(e.start_ns + e.duration_ns), e) for e in line.events]
            if not any(n.startswith("bench.") for n, _, _, _ in evs):
                continue
            steps += [(a, b) for n, a, b, _ in evs if n == "bench.step"]
            program += [phases.Span(n, a, b, dict(e.stats)
                                    if n == "engine.prefill" else {})
                        for n, a, b, e in evs
                        if n.startswith(phases.PROGRAM_PREFIXES)]
    return phases.nest(program), sorted(steps)


@pytest.fixture(scope="module")
def chip_profile():
    import jax
    return jax.profiler.ProfileData.from_file(str(SPAN_TRACE))


def test_a_chip_trace_with_program_spans_adds_up(chip_profile):
    """A traced run of the tiny test cell on one TPU v5 lite
    (``tools/record_trace.py --seconds 1``): the idle split adds up to
    the idle of ``lib/trace.py``, and the program's spans cover at least
    nine tenths of the idle inside the harness's ``bench.step``."""
    s = trace.summarize(chip_profile)
    p = phases.summarize(chip_profile)
    assert p is not None and list(s.busy_s) == [0]
    assert p.window_ns * 1e-9 == pytest.approx(s.window_s)
    assert sum(p.idle_ns.values()) * 1e-9 == pytest.approx(
        s.window_s - s.busy_s[0], rel=1e-6)
    assert p.idle_ns.get("engine.logits", 0) > 0
    assert p.idle_ns.get("engine.sample", 0) > 0
    assert set(p.idle_ns) - {phases.NO_SPAN} <= {
        "cluster.step", "cluster.commit_ready", "cluster.handoff",
        "engine.step", "engine.admit", "engine.prefill", "engine.compact",
        "engine.decode", "engine.logits", "engine.sample"}
    ordered, steps = spans_of(chip_profile)
    busy = trace.merge([(float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for pl in chip_profile.planes if pl.name ==
                        "/device:TPU:0" for ln in pl.lines
                        if ln.name == trace.OPS_LINE for e in ln.events])
    idle = [g for a, b in steps for g in trace.gaps(trace.clip(busy, a, b),
                                                     a, b)]
    split = phases.attribute(idle, phases.innermost(ordered))
    covered = sum(v for n, v in split.items() if n != phases.NO_SPAN)
    assert covered >= 0.9 * sum(split.values()) > 0


def test_chip_prefills_follow_the_tiny_cell_s_ladder(chip_profile):
    ladder = [8, 16, 32, 64, 128]          # s_max 128 (tests/data/cells)
    ordered, _ = spans_of(chip_profile)
    pre = [s for s in ordered if s.name == "engine.prefill"]
    assert pre
    for s in pre:
        n, b = s.args["prompt_len"], s.args["bucket"]
        assert b == next(x for x in ladder if x >= n)
        assert [c.name for c in s.children] == ["engine.logits",
                                                "engine.sample"]
    p = phases.summarize(chip_profile)
    assert p.holds_ns and min(p.holds_ns) > 0
    assert 0 < p.prompt_tokens < p.bucket_tokens


def test_readers_on_the_chip_trace(chip_profile, tmp_path, monkeypatch):
    d = tmp_path / "plugins" / "profile" / "chip"
    d.mkdir(parents=True)
    shutil.copy(SPAN_TRACE, d / "chip.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    run = FakeRun()
    run.trace = trace.summarize(chip_profile)
    got = {n: read(n, run) for n in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    idle = read("idle_share.serve", run)
    rest = phases.for_run(run).idle_pct({phases.NO_SPAN})
    assert got["idle_sample.serve"] + got["idle_schedule.serve"] + rest \
        == pytest.approx(idle, abs=1e-6)
