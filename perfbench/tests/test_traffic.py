"""The traffic generator gives every seed the same work.  By hand:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_traffic.py
"""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE)]

import traffic  # noqa: E402

SEEDS = (1, 2**31 + 7, 2**33 + 5)


def window(mix, seed, seconds=51.0):
    plan = traffic.schedule(mix, seconds, seed, 1000, 4096)
    return [p for p in plan if 0.0 <= p.due_s < seconds]


@pytest.mark.parametrize("name", ["chat", "code"])
def test_same_work_in_the_window_on_every_seed(name):
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    runs = [window(mix, s) for s in SEEDS]
    for w in runs:
        assert [p.due_s for p in w] == [p.due_s for p in runs[0]]
        assert sorted(len(p.prompt) for p in w) == sorted(len(p.prompt) for p in runs[0])
        assert sorted(p.max_new for p in w) == sorted(p.max_new for p in runs[0])
    assert len({tuple(len(p.prompt) for p in w) for w in runs}) == 1
    # the prompt tokens are the seed's own
    assert runs[0][0].prompt.tolist() != runs[1][0].prompt.tolist()
