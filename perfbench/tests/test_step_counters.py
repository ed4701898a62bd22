"""The readers of the engine's own step counters, on fabricated snapshots.

``host_syncs_per_step`` and ``step_host_ms`` read two registry snapshots
(``Engine.metrics()``) taken at the edges of the measured window.  Run by
hand from the repo root:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_step_counters.py
"""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]


def reader(name):
    spec = importlib.util.spec_from_file_location(name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def snap(steps, syncs, host_count, host_sum):
    return {"counters": {"engine_steps_total": steps, "host_syncs_total": syncs},
            "histograms": {"step_host_ms": {"count": host_count, "sum": host_sum}}}


def window(a, b):
    return SimpleNamespace(snap0=a, snap1=b)


def test_host_syncs_per_step():
    read = reader("host_syncs_per_step").read
    # 40 steps, 280 syncs in the window: 12-slot host sampling at ~6 slots
    assert read(window(snap(100, 500, 100, 0.0), snap(140, 780, 140, 0.0))) == 7.0
    assert read(window(snap(7, 9, 7, 0.0), snap(11, 14, 11, 0.0))) == pytest.approx(1.25)


def test_step_host_ms():
    read = reader("step_host_ms").read
    assert read(window(snap(100, 0, 100, 1000.0), snap(140, 0, 140, 3000.0))) == 50.0
    assert read(window(snap(7, 0, 7, 10.0), snap(11, 0, 11, 42.5))) == pytest.approx(8.125)


@pytest.mark.parametrize("name", ["host_syncs_per_step", "step_host_ms"])
def test_nothing_to_read(name):
    read = reader(name).read
    # the window holds no step
    assert read(window(snap(10, 30, 10, 5.0), snap(10, 30, 10, 5.0))) is None
    # the window never opened or closed
    assert read(window(None, snap(10, 30, 10, 5.0))) is None
    assert read(window(snap(10, 30, 10, 5.0), None)) is None
    # a program that keeps neither counter
    bare = {"counters": {"engine_steps_total": 10}, "histograms": {}}
    assert read(window(bare, {"counters": {"engine_steps_total": 20},
                              "histograms": {}})) is None
