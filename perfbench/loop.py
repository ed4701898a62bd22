"""The open loop around ``repro.serve.Engine``: submit what is due, step.

The timed path is the engine's own: ``Engine.submit`` and ``Engine.step``
with the Pallas paged-decode kernel.  The loop keeps the schedule of due
times on the host clock, submits every request that is due before each
``step()``, sleeps when the engine has no work, and stamps each token when
``step()`` returns with it on the host (every cell sets ``eos_id``, so the
engine brings each step's tokens to the host).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from traffic import Planned

clock = time.perf_counter


@dataclasses.dataclass
class Served:
    plan: Planned
    req: object = None  # the engine's Request
    t_submit: float = float("nan")  # window-relative seconds
    token_t: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.state == "finished"


@dataclasses.dataclass
class Window:
    seconds: float
    t0: float  # host clock at the window's start
    served: List[Served]
    steps: List[tuple]  # (window-relative end time, engine step count)
    t_end: float  # window-relative time at which the loop stopped

    def missing(self) -> List[Served]:
        """Requests due inside the window that got no first token before
        the loop stopped: answers that never came."""
        return [s for s in self.measured() if not s.token_t]

    def measured(self) -> List[Served]:
        """Requests due inside the window."""
        return [s for s in self.served if 0.0 <= s.plan.due_s < self.seconds]


def run(eng, plan: List[Planned], seconds: float, *, drain_s: float,
        before_step: Optional[Callable[[float], None]] = None) -> Window:
    """Serve ``plan`` open-loop; the window is [0, seconds) after the first
    ``-plan[0].due_s`` seconds of traffic.  After the window the loop goes on
    (arrivals too) until every request due in the window has its first
    token, or ``drain_s`` more seconds have passed.  ``before_step`` is
    called with the window-relative time before each engine step, after
    that step's submissions."""
    served = [Served(p) for p in plan]
    t0 = clock() - plan[0].due_s  # plan[0] is due now
    nxt = 0
    live: List[Served] = []
    steps = []
    waiting = [s for s in served if 0.0 <= s.plan.due_s < seconds]
    while True:
        now = clock() - t0
        if now >= seconds:
            waiting = [s for s in waiting if not s.token_t]
            if not waiting or now >= seconds + drain_s:
                break
        while nxt < len(served) and served[nxt].plan.due_s <= now:
            s = served[nxt]
            s.req = eng.submit(s.plan.prompt, s.plan.max_new, rid=s.plan.rid,
                               arrival_step=eng.step_count)
            s.t_submit = clock() - t0
            live.append(s)
            nxt += 1
        if not eng.sched.has_work():
            if nxt >= len(served):
                break
            wait = served[nxt].plan.due_s - (clock() - t0)
            if wait > 0:
                time.sleep(min(wait, 0.05))
            continue
        if before_step is not None:
            before_step(clock() - t0)
        eng.step()
        t = clock() - t0
        steps.append((t, eng.step_count))
        still = []
        for s in live:
            n = len(s.req.out_tokens)
            if n > len(s.token_t):
                s.token_t.extend([t] * (n - len(s.token_t)))
            if s.req.state != "finished":
                still.append(s)
        live = still
    return Window(seconds, t0, served, steps, clock() - t0)


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation (numpy's)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(w: Window) -> Dict[str, float]:
    """The window's end-to-end numbers, all requests pooled.  A request due
    in the window that got no first token counts with the time from its due
    time to the loop's end, a bound below its TTFT, so a stalled request
    makes the percentile worse rather than leaving it."""
    meas = w.measured()
    ttft = [((s.token_t[0] if s.token_t else w.t_end) - s.plan.due_s) * 1e3
            for s in meas]
    gaps = []
    tokens = 0
    for s in w.served:
        ts = s.token_t
        tokens += sum(1 for t in ts if 0.0 <= t < w.seconds)
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:])
                    if 0.0 <= b < w.seconds)
    late = [(s.t_submit - s.plan.due_s) * 1e3 for s in meas
            if not np.isnan(s.t_submit)]
    return {
        "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
        "ttft_p90_ms": percentile(ttft, 90) if ttft else None,
        "itl_p50_ms": percentile(gaps, 50) if gaps else None,
        "itl_p95_ms": percentile(gaps, 95) if gaps else None,
        "output_tok_per_s": tokens / w.seconds,
        "_n_ttft": len(ttft), "_missing": len(w.missing()),
        "_n_itl": len(gaps), "_tokens": tokens,
        "_late_p50_ms": percentile(late, 50) if late else None,
        "_late_max_ms": max(late) if late else None,
    }
