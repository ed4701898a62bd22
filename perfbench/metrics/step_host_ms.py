"""step_host_ms: engine steps (``serve/engine.py``) host time per step.

The engine's ``step_host_ms`` histogram: each step's wall time (its
``serve.step`` span) less the time its ``serve.sync`` spans wait on the
device; the mean over the steps of the traced run's measured window, in
ms.  None where the window holds no step or the program keeps no such
histogram.  Moves, by cell: ``step_host_ms.ttft_p50`` moves
``ttft_p50_ms`` (chat), ``step_host_ms.itl_p95`` moves ``itl_p95_ms``
(code).
"""


def read(ctx):
    if ctx.snap0 is None or ctx.snap1 is None:
        return None
    h1 = ctx.snap1["histograms"].get("step_host_ms")
    if h1 is None:
        return None
    h0 = ctx.snap0["histograms"]["step_host_ms"]
    steps = h1["count"] - h0["count"]
    return (h1["sum"] - h0["sum"]) / steps if steps > 0 else None
