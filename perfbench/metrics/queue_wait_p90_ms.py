"""queue_wait_p90_ms: scheduler (``serve/scheduler.py``) admission wait.

From each request's due time to the ``admitted`` mark that the scheduler
writes on the request's timeline (``Request.stats.t_admitted``, the same
host clock as the loop's), over the requests due in the traced run's
window; the 90th percentile, in ms.  Moves ``ttft_p50_ms``.
"""
import numpy as np


def read(ctx):
    w = ctx.window
    waits = [(s.req.stats.t_admitted - (w.t0 + s.plan.due_s)) * 1e3
             for s in w.measured()
             if s.req is not None and "admitted" in s.req.timeline.marks]
    return float(np.percentile(waits, 90)) if waits else None
