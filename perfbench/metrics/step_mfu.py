"""step_mfu: the whole model step.

Model FLOPs of the tokens the traced sub-window processed (the family's
``step_flops``: decode tokens with attention over their contexts, prefill
chunks' real prompt tokens), over the window's length times the chips'
bf16 peak, in %.  Moves, by
cell: ``step_mfu.ttft_p50`` moves ``ttft_p50_ms``
(chat), ``step_mfu.itl_p95`` moves ``itl_p95_ms`` (code).
"""


def read(ctx):
    if not ctx.decode_ctx and not ctx.chunks:
        return None
    chips = len(ctx.red["devices"])
    f = ctx.family.step_flops(ctx.spec, ctx.decode_ctx, ctx.chunks)
    return 100.0 * f / (ctx.red["window_ns"] / 1e9 * chips
                        * ctx.peaks["bf16_flops_per_s"])
