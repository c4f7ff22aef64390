"""Sharded L2 search, per chip: the least time the chip could take to score
its own shard (each of its live float32 rows and its valid flag read once,
or the batch's score FLOPs at peak, whichever is larger) over the device
time of the collective program's ops on that shard's arrays, the ops whose
shapes carry the shard's row count (the score product, the mask, the local
top-k, the counter touches); the lowest chip's share. A chip's rows are
never divided by another chip's time: each chip is taken alone, with the
fewest live rows any shard holds."""
UNIT = "%"
MODULE = r"^jit_body(\(|$)"


def read(run):
    import re

    from flops import roofline_s, topk_bytes, topk_flops
    from xtrace import executions, ops_within

    if run.trace is None or not run.shard_rows:
        return None
    cap = run.cfg["l2_rows"] // run.cfg["shards"]
    rx = re.compile(rf"[\[,]{cap}[\],]")
    rows = min(run.shard_rows)
    sizes = [len(c.texts) for c in run.calls]
    b = max(1 << (max(sizes) - 1).bit_length(), 1) if sizes else 1
    t_min = roofline_s(topk_flops(b, rows, run.dim), topk_bytes(rows, run.dim), run.peak)
    shares = []
    for dev, mods in executions(run.trace, MODULE):
        t = sum(s for e, s in ops_within(dev.ops, mods) if rx.search(e.name))
        if t > 0:
            shares.append(100.0 * t_min * len(mods) / t)
    return min(shares) if shares else None
