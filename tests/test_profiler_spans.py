"""The serving step's spans in the `jax.profiler` trace, and the
admission stamp.

A tiny paged engine serves a few requests under a profiler session on
the CPU backend; the `.xplane.pb` it writes is read back with
`jax.profiler.ProfileData`, and the program's spans must nest as
docs/observability.md ("Profiler spans") lists them, with the prefill's
``prompt_len``/``bucket`` args following the engine's bucket ladder.
"""
import dataclasses
from contextlib import nullcontext
from typing import Dict, List, Optional

import jax
import numpy as np
import pytest

from repro.obs import Recorder, recording
from repro.serving import Request, ServingCluster, ServingEngine
from repro.serving.clock import FakeClock, install_clock
from repro.sharding import ShardingPlan

PROGRAM = ("cluster.", "engine.")
PROMPTS = (5, 8, 11, 20, 3)
NEW = 3


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int
    args: Dict[str, int]
    children: List["Span"] = dataclasses.field(default_factory=list)
    parent: Optional["Span"] = None


def profiled(fn, trace_dir, names=PROGRAM):
    """Run ``fn`` under a profiler session; its result and the spans of
    the trace whose names start with one of ``names``, nested."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    roots: List[Span] = []
    for plane in data.planes:
        for line in plane.lines:
            evs = [Span(e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for e in line.events if e.name.startswith(names)]
            stack: List[Span] = []
            for s in sorted(evs, key=lambda s: (s.start, -s.end)):
                while stack and stack[-1].end < s.end:
                    stack.pop()
                if stack:
                    s.parent = stack[-1]
                    stack[-1].children.append(s)
                else:
                    roots.append(s)
                stack.append(s)
    return out, roots


def walk(spans):
    for s in spans:
        yield s
        yield from walk(s.children)


def serve(model, params, prompts=PROMPTS):
    """A one-engine cluster with the padded-bucket prefill ladder; the
    requests are submitted at once (two lanes: the rest queue)."""
    cluster = ServingCluster()
    eng = ServingEngine(model, params, n_slots=2, s_max=32)
    cluster.spawn_engine("e0", eng, prefill_buckets=True)
    rng = np.random.default_rng(7)
    reqs = [Request(i, rng.integers(2, model.cfg.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=NEW)
            for i, n in enumerate(prompts)]

    def run():
        for r in reqs:
            cluster.submit(r)
        cluster.run()
        return {r.rid: list(r.tokens_out) for r in reqs}
    return eng, run


@pytest.fixture(scope="module")
def traced(fp32_model, tmp_path_factory):
    _, model, params = fp32_model
    eng, run = serve(model, params)
    streams, roots = profiled(run, tmp_path_factory.mktemp("trace"))
    return eng, streams, roots


def test_step_spans_nest_as_documented(traced):
    _, _, roots = traced
    steps = [s for s in roots if s.name == "cluster.step"]
    assert steps and len(steps) == len(roots)
    decoded = 0
    for step in steps:
        assert [c.name for c in step.children] == [
            "cluster.commit_ready", "engine.step", "cluster.handoff"]
        eng = step.children[1]
        names = [c.name for c in eng.children]
        assert names in (["engine.admit", "engine.compact"],
                         ["engine.admit", "engine.compact", "engine.decode",
                          "engine.logits", "engine.sample"])
        decoded += len(names) == 5
        for pre in eng.children[0].children:
            assert pre.name == "engine.prefill"
            assert [c.name for c in pre.children] == ["engine.logits",
                                                      "engine.sample"]
    assert decoded >= NEW - 1
    prefills = [s for s in walk(roots) if s.name == "engine.prefill"]
    assert len(prefills) == len(PROMPTS)
    for s in walk(roots):                   # children in order, inside
        for a, b in zip(s.children, s.children[1:]):
            assert s.start <= a.start <= a.end <= b.start <= b.end <= s.end


def test_prefill_args_follow_the_bucket_ladder(traced):
    eng, _, roots = traced
    ladder = eng.bucket_lengths()
    assert ladder == [8, 16, 32]
    got = [(s.args["prompt_len"], s.args["bucket"]) for s in walk(roots)
           if s.name == "engine.prefill"]
    assert sorted(got) == sorted(
        (n, next(b for b in ladder if b >= n)) for n in PROMPTS)
    for s in walk(roots):                   # the only span args
        assert not s.args or s.name in ("engine.prefill", "engine.decode")


def test_decode_args_count_the_pages_read(traced):
    """On the CPU the paged engine decodes on the gather path, which
    reads every lane's whole page extent each step."""
    eng, _, roots = traced
    decodes = [s for s in walk(roots) if s.name == "engine.decode"]
    assert decodes and eng.kv_path == "dense"
    for s in decodes:
        assert s.args == {"kv_path": "dense",
                          "kv_pages": eng.n_slots * eng.pages_per_seq}


def test_served_tokens_identical_with_the_profiler_on_and_off(
        traced, fp32_model):
    _, model, params = fp32_model
    _, run = serve(model, params)
    assert run() == traced[1]
    assert all(len(t) == NEW for t in traced[1].values())


def test_recorder_spans_reach_the_profiler_trace(fp32_model, tmp_path):
    _, model, params = fp32_model
    _, run = serve(model, params, prompts=(5, 9))
    with recording(Recorder()) as rec:
        _, roots = profiled(run, tmp_path, names=PROGRAM + ("route",))
    routes = [s for s in roots if s.name == "route"]
    assert len(routes) == len(rec.trace.spans("route")) == 2


PINNED = ShardingPlan(device_constraints=(("pod", 0),),
                      forbidden_collective_axes=("pod",))


def test_migrate_and_swap_commit_are_profiler_spans(fp32_model, tmp_path):
    """The paper's pauses on the profiler clock: a live migration and a
    reconfiguration's blocking swap window."""
    cfg, model, params = fp32_model
    cluster = ServingCluster()
    cluster.register("src", ServingEngine(model, params, n_slots=2,
                                          s_max=32))
    cluster.register("dst", ServingEngine(model, params, n_slots=2,
                                          s_max=32))
    rng = np.random.default_rng(3)
    for rid in range(2):
        cluster.engine("src").submit(Request(
            rid, rng.integers(2, cfg.vocab_size, size=6).astype(np.int32),
            max_new_tokens=6))
    cluster.step()

    def move():
        cluster.migrate_requests("src", "dst")
        return cluster.reconfigure("dst", PINNED, prefill_lengths=(6,))
    report, roots = profiled(move, tmp_path)
    assert [s.name for s in roots] == ["cluster.migrate",
                                       "cluster.swap_commit"]
    window = roots[1]
    assert (window.end - window.start) * 1e-9 <= report.downtime_s
    cluster.run()
    assert all(len(r.tokens_out) == 6 for r in cluster.engine("dst").done)


def lane_busy_run(model, params, clock, rec=None):
    """One decode lane: request 1 queues behind request 0 until its lane
    frees. Returns every stamp and token, and the recorder's admits."""
    restore = install_clock(clock)
    try:
        eng = ServingEngine(model, params, n_slots=1, s_max=32)
        rng = np.random.default_rng(5)
        reqs = [Request(i, rng.integers(2, model.cfg.vocab_size, size=6)
                        .astype(np.int32), max_new_tokens=4)
                for i in range(2)]
        with recording(rec) if rec is not None else nullcontext():
            for r in reqs:
                eng.submit(r)
            eng.run()
    finally:
        restore()
    return [(r.t_submit, r.t_admit, r.t_first, r.t_done,
             tuple(r.tokens_out)) for r in reqs]


def test_queue_wait_is_the_wait_for_admission_not_the_prefill(fp32_model):
    _, model, params = fp32_model
    rec = Recorder()
    stamps = lane_busy_run(model, params, FakeClock(), rec)
    admits = {e.rid: e.data["queue_wait_s"]
              for e in rec.events("request.admit")}
    for rid, (t_submit, t_admit, t_first, _, _) in enumerate(stamps):
        assert admits[rid] == t_admit - t_submit
        assert t_submit <= t_admit < t_first
    # the queued request waited for the lane: at least request 0's
    # decode, and its wait ends where its own prefill starts
    assert admits[1] > stamps[0][3] - stamps[0][2] > 0
    assert stamps[1][1] >= stamps[0][3]
    waits = rec.metrics.histogram("queue_wait_s", label="*")
    assert waits.count == 2


def test_admission_stamp_keeps_fake_clock_replays_bit_identical(
        fp32_model):
    _, model, params = fp32_model
    rec = Recorder()
    with_rec = lane_busy_run(model, params, FakeClock(), rec)
    without = lane_busy_run(model, params, FakeClock())
    assert with_rec == without
    kinds = {e.kind for e in rec.events()}
    assert kinds == {"request.submit", "request.admit", "request.complete"}
