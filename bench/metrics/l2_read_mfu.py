"""Collective read program: the FLOPs the traced window's lookups require
(the encoder once over each request's real tokens, plus one score per live
L2 row; the L1's at most 4,096 rows are left out) over the slowest chip's
mean device time of an execution times the chips' bf16 peak. The whole
step's share of the chips' peak: it still bounds a gain once a kernel is
gone."""
UNIT = "%"
MODULE = r"^jit_body(\(|$)"


def read(run):
    from flops import encoder_flops, search_flops
    from reference import n_tokens
    from xtrace import executions, reads_in_window

    if run.trace is None or not run.shard_rows:
        return None
    per = [sum(e.dur for e in evs) / len(evs) for _, evs in executions(run.trace, MODULE)]
    seqs = set(reads_in_window(run.trace))
    if not per or not seqs:
        return None
    enc, rows = run.cfg["embedder"], sum(run.shard_rows)
    fl = [sum(encoder_flops(n_tokens(t), enc) + search_flops(rows, run.dim)
              for t in c.texts) for c in run.calls if c.seq in seqs]
    if not fl:
        return None
    chips = run.cfg["shards"]
    return 100.0 * (sum(fl) / len(fl)) / (max(per) * chips * run.peak["bf16_flops_per_s"])
