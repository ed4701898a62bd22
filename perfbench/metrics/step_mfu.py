"""step_mfu: the whole model step.

Model FLOPs of the tokens the traced sub-window processed, over the
window's length times the chips' bf16 peak, in %.  A decode token counts 2
x the layers' and the head's matrix parameters plus attention over its
context (4 x layers x heads x head size per context token); a prefill
chunk counts its real prompt tokens only (no bucket padding): 2 x the
layers' parameters per token, causal attention over its offset, and the
head once, for the first token of the answer.  Moves, by
cell: ``step_mfu.ttft_p50`` moves ``ttft_p50_ms``
(chat), ``step_mfu.itl_p95`` moves ``itl_p95_ms`` (code).
"""
import model as MB


def flops(spec, decode_ctx, chunks):
    m = MB.dims(spec)
    p = MB.param_count(spec)
    att = 4 * m["L"] * m["H"] * m["dh"]  # per (query, key) pair
    dec = sum(len(c) * 2 * (p["layers"] + p["head"]) + att * sum(c)
              for c in decode_ctx)
    pre = sum(2 * p["layers"] * n + att * (n * off + n * (n + 1) // 2)
              + (2 * p["head"] if final else 0) for off, n, final in chunks)
    return dec + pre


def read(ctx):
    if not ctx.decode_ctx and not ctx.chunks:
        return None
    chips = len(ctx.red["devices"])
    f = flops(ctx.spec, ctx.decode_ctx, ctx.chunks)
    return 100.0 * f / (ctx.red["window_ns"] / 1e9 * chips
                        * ctx.peaks["bf16_flops_per_s"])
