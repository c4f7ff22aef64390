"""Collective read program: device time of its collectives (all-gather and
any other cross-chip op) per execution, on each chip, from the device
trace; the slowest chip's. The program exchanges only each shard's [B, k]
candidates, so this is the price of the exchange, waits included."""
UNIT = "ms"
MODULE = r"^jit_body(\(|$)"
COLLECTIVE = r"all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter"


def read(run):
    import re

    from xtrace import executions, op_name, ops_within

    if run.trace is None:
        return None
    rx, per = re.compile(COLLECTIVE), []
    for dev, mods in executions(run.trace, MODULE):
        t = sum(s for e, s in ops_within(dev.ops, mods) if rx.search(op_name(e.name)))
        per.append(t / len(mods))
    return 1e3 * max(per) if per else None
