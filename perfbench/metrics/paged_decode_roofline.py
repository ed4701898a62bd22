"""paged_decode_roofline: kernels (``kernels/paged_attention.py``).

The least time the chip could take for the decode kernel's work in the
traced sub-window, over the kernel's summed device time, in %.  The
kernel is the family's (``decode_kernel``: ``paged_attention_decode`` in
the dense family), matched by its name inside the decode-step program; its
work is the algorithm's, from shapes and positions (the family's
``decode_work``), not what an implementation moves, so it reads the same
work whatever implements it.  Moves, by
cell: ``paged_decode_roofline.ttft_p50`` moves ``ttft_p50_ms``
(chat), ``paged_decode_roofline.itl_p95`` moves ``itl_p95_ms`` (code).
"""


def read(ctx):
    kernel, _ = ctx.family.decode_kernel(ctx.spec)
    t = ctx.trace.op_seconds(ctx.red, ctx.trace.kernel_op(kernel),
                             within=ctx.trace.DECODE_PROGRAM)
    if not t or not ctx.decode_ctx:
        return None
    nbytes, flops = ctx.family.decode_work(ctx.spec, ctx.decode_ctx)
    chips = len(ctx.red["devices"])
    least = max(nbytes / chips / ctx.peaks["hbm_bytes_per_s"],
                flops / chips / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / t
