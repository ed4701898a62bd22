"""paged_decode_roofline: kernels (``kernels/paged_attention.py``).

The least time the chip could take for the paged-decode kernel's work in
the traced sub-window, over the kernel's summed device time, in %.  The
work is the algorithm's, from shapes and positions: for each decode call
and each layer, the K and V of every active slot's context, plus its query
and output, read or written once; and 4 x heads x head size FLOPs per
context token.  It counts what the algorithm needs, not what an
implementation moves, so it reads the same work whatever implements it.
Moves, by
cell: ``paged_decode_roofline.ttft_p50`` moves ``ttft_p50_ms``
(chat), ``paged_decode_roofline.itl_p95`` moves ``itl_p95_ms`` (code).
"""
import model as MB

# the Pallas kernel's op in the device trace: a tpu_custom_call (the kernel
# has no name of its own there) inside the decode-step program
KERNEL = r'custom_call_target="tpu_custom_call"'
DECODE = r"^jit_(_unknown|_paged_step)\b"
BYTES = 2  # bf16


def work(spec, decode_ctx):
    """(bytes, flops) of the kernel over the recorded decode calls; each
    entry of ``decode_ctx`` lists the active slots' context lengths."""
    m = MB.dims(spec)
    kv_row = 2 * m["Hkv"] * m["dh"] * BYTES  # K and V of one token, one layer
    qo_row = 2 * m["H"] * m["dh"] * BYTES  # query in, output out
    ctx_tokens = sum(sum(c) for c in decode_ctx)
    slots = sum(len(c) for c in decode_ctx)
    nbytes = m["L"] * (ctx_tokens * kv_row + slots * qo_row)
    flops = m["L"] * 4 * m["H"] * m["dh"] * ctx_tokens
    return nbytes, flops


def read(ctx):
    t = ctx.trace.op_seconds(ctx.red, KERNEL, within=DECODE)
    if not t or not ctx.decode_ctx:
        return None
    nbytes, flops = work(ctx.spec, ctx.decode_ctx)
    chips = len(ctx.red["devices"])
    least = max(nbytes / chips / ctx.peaks["hbm_bytes_per_s"],
                flops / chips / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / t
