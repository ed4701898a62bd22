"""The check that decides ``correct`` fails what it must, on the CPU.

Each test drives a whole rehearsal run (``run.py --rehearse``: weights,
engine, warm-up, open-loop window, check) with the timed path broken
underneath, and sees ``correct`` come out false; the sound run sees it
true.  Run by hand from the repo root (a few minutes):

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_check.py

The faults are those a one-chip serving cell can have: a decode step that
returns its state unchanged, half of the batch left out, a token altered
where it is produced; and the control, the reference in float8 in the
program's place.  (No cell spans chips, so no exchange can be left out.)
"""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import check  # noqa: E402
import faults  # noqa: E402
import run  # noqa: E402
from repro.serve import engine as E  # noqa: E402

CELLS = ["minicpm-2b.chat", "starcoder2-7b-pp2.code"]
SEED = 2**31 + 99


def serve(capsys, cell):
    run.main(["--workload", cell, "--seed", str(SEED), "--seconds", "3",
              "--trace", "0", "--rehearse"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, cell):
    assert serve(capsys, cell)["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_is_not_correct(capsys, monkeypatch, cell, fault):
    faults.plant(monkeypatch.setattr, fault)
    res = serve(capsys, cell)
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_fp8_control_is_not_correct(capsys, monkeypatch, cell):
    real = check.compare
    monkeypatch.setattr(check, "compare",
                        lambda *a, **k: real(*a, **k, control="fp8"))
    res = serve(capsys, cell)
    assert res["correct"] is False, res["compared"]


def test_stalled_admission_is_not_correct(capsys, monkeypatch):
    """A request that never reaches its first token fails the run."""
    from repro.serve import scheduler as S

    real = S.Scheduler.admit

    def admit(self, step):
        head = self.queue[0] if self.queue else None
        if head is not None and 2 <= head.rid < 10**9:  # served traffic
            return []  # head-of-line: everything behind it waits too
        return real(self, step)

    monkeypatch.setattr(S.Scheduler, "admit", admit)
    res = serve(capsys, "starcoder2-7b-pp2.code")
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] > 0
    assert res["compared"]["requests_without_first_token"]["value"] == res["failed"]


def test_missing_first_token_counts_in_ttft():
    """TTFT is over every request due in the window: one with no first
    token counts up to the loop's end, never drops out."""
    import loop
    import numpy as np
    from traffic import Planned

    def served(due, first):
        s = loop.Served(Planned(0, due, np.zeros(4, np.int32), 2))
        s.token_t = [] if first is None else [first, first + 0.1]
        return s

    ok = [served(i, i + 0.5) for i in range(3)]
    w = loop.Window(10.0, 0.0, ok, [], 12.0)
    stalled = loop.Window(10.0, 0.0, ok[:1] + [served(1, None), served(2, None)],
                          [], 12.0)
    assert loop.end_to_end(w)["ttft_p50_ms"] == 500.0
    assert stalled.missing() == stalled.served[1:]
    assert loop.end_to_end(stalled)["ttft_p50_ms"] == 10000.0  # 0.5, 11, 10 s
