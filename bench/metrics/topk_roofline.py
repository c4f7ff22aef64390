"""Lane top-k kernel (``kernels/similarity_topk``): the least time the chip
could take for each kernel call (every live float32 row and its valid flag
read once, or the batch's score FLOPs at peak, whichever is larger), over the
kernel's device time, summed over its calls in the traced window."""
UNIT = "%"
KERNEL = r"^similarity_topk_lanes_blocks$"  # the pallas_call's XLA op


def read(run):
    from flops import roofline_s, topk_bytes, topk_flops
    from xtrace import matching

    if run.trace is None:
        return None
    evs = matching(run.trace.ops, KERNEL, run.trace, ops=True)
    if not evs:
        return None
    sizes = [len(c.texts) for c in run.calls]
    # the batch bucket each kernel call computes; the byte term binds anyway
    b = max(1 << (max(sizes) - 1).bit_length(), 1) if sizes else 1
    t_min = roofline_s(topk_flops(b, run.live_rows, run.dim),
                       topk_bytes(run.live_rows, run.dim), run.peak)
    return 100.0 * t_min * len(evs) / sum(e.dur for e in evs)
