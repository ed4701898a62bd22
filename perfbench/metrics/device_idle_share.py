"""device_idle_share: device.

One minus the union of the device's op intervals over the traced
sub-window, in % (averaged over the chips used).  Moves, by
cell: ``device_idle_share.ttft_p50`` moves ``ttft_p50_ms``
(chat), ``device_idle_share.itl_p95`` moves ``itl_p95_ms`` (code).
"""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_s(ctx.red) * 1e9 / ctx.red["window_ns"])
