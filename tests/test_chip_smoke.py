"""chip_smoke.py off the chip: its phase A passes at reduced widths on the
CPU (in this process), and its verdict never says ``"ok": true`` unless
JAX runs on a TPU."""
import importlib.util
import sys
from pathlib import Path

import pytest

from repro.configs import get_reduced_config
from repro.models import build_model

ROOT = Path(__file__).resolve().parents[1]
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # its dataclasses look the module up
    spec.loader.exec_module(mod)
    return mod


def test_phase_a_passes_at_reduced_widths(smoke):
    model = build_model(get_reduced_config(smoke.ARCH))
    params = smoke.init_params(model, seed=0)
    out = smoke.phase_a(model, params, smoke.REDUCED, seed=0,
                        log=lambda msg: None)
    assert out["migrated"] > 0
    assert out["compiles"] and not any(out["compiles"].values())


def test_verdict_refuses_ok_off_the_tpu(smoke):
    passed = {"A": True, "B": True}
    assert smoke.verdict(passed, smoke.device_info(), 1)["ok"] is False
    assert smoke.verdict(passed, TPU, 1) == {"ok": True, "device": TPU}
    assert smoke.verdict({"A": True, "B": False}, TPU, 1)["ok"] is False
    assert smoke.verdict({}, TPU, 1)["ok"] is False
    assert smoke.verdict({"4chip": True}, TPU, 4)["ok"] is False


def test_main_exits_nonzero_without_a_tpu(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "configure_compile_cache", lambda: "unset")
    assert smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out and "no TPU" in out
