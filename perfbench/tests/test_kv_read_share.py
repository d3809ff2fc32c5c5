"""``kv_read_share.serve``: the share of the lanes' page extent that the
traced window's decode steps read, from the ``engine.decode`` spans'
``kv_pages``, on a made-up trace and on a trace recorded before the
spans carried it."""
import dataclasses
import shutil
import types
from typing import Dict, List

import pytest

from conftest import BENCH
from lib import harness

DATA = BENCH / "tests" / "data"
SPAN_TRACE = DATA / "tiny_chip_spans.xplane.pb"     # spans without kv_pages
NAME = "kv_read_share.serve"
LAYOUT = {"engines": [{"n_slots": 4, "s_max": 128, "page_size": 16}]}


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)


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


def made_up(path="pages"):
    """Three decode spans inside the harness's window, one after it,
    and one on another thread; the extent is 4 lanes x 8 pages."""
    def decode(t, n):
        return Ev("engine.decode", t, 5, {"kv_path": path, "kv_pages": n})
    main = [Ev("bench.step", 0, 100), decode(10, 3), decode(40, 5),
            Ev("bench.step", 100, 100), decode(150, 8), decode(250, 32)]
    return Profile([
        Plane("/host:CPU", [Line("main", main),
                            Line("worker", [decode(60, 32)])]),
        Plane("/device:TPU:0", [Line("XLA Ops", [decode(70, 32)])])])


def run_on(tmp_path, monkeypatch, profile=None, src=SPAN_TRACE):
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    shutil.copy(src, d / "t.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    if profile is not None:
        import jax
        monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                            lambda path: profile)
    return types.SimpleNamespace(trace=object(),
                                 cell=types.SimpleNamespace(layout=LAYOUT))


@pytest.mark.parametrize("path, pages, want", [
    ("pages", None, (3 + 5 + 8) / (3 * 32) * 100),
    ("dense", 32, 100.0),
])
def test_share_of_the_window_s_decode_spans(tmp_path, monkeypatch, path,
                                            pages, want):
    profile = made_up(path)
    if pages is not None:
        for plane in profile.planes:
            for line in plane.lines:
                for ev in line.events:
                    if "kv_pages" in ev.stats:
                        ev.stats["kv_pages"] = pages
    run = run_on(tmp_path, monkeypatch, profile)
    assert harness.read_metric(NAME, run) == pytest.approx(want)


def test_nothing_to_read(tmp_path, monkeypatch):
    """An untraced run, and a trace whose decode spans carry no
    ``kv_pages`` (a program before the counter), read None."""
    run = run_on(tmp_path, monkeypatch)
    assert harness.read_metric(NAME, run) is None
    run.trace = None
    assert harness.read_metric(NAME, run) is None
