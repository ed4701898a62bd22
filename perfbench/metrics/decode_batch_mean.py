"""decode_batch_mean: scheduler (``serve/scheduler.py``) decode batch size.

The mean number of slots in a decode step, over the decode steps of the
traced run's window, from the engine's ``decode_batch_occupancy``
histogram (steps that ran no decode are left out).  Moves
``output_tok_per_s``.
"""


def read(ctx):
    if ctx.snap0 is None or ctx.snap1 is None:
        return None
    h0 = ctx.snap0["histograms"]["decode_batch_occupancy"]
    h1 = ctx.snap1["histograms"]["decode_batch_occupancy"]
    steps = (h1["count"] - h1["counts"][0]) - (h0["count"] - h0["counts"][0])
    return (h1["sum"] - h0["sum"]) / steps if steps > 0 else None
