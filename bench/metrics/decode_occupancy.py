"""Serving engine: live slots per decode step over the engine's slots, over
the window: the decode tokens of the window's generations (each one's
tokens less the first, which its prefill gives) over the engine's decode
steps (its ``decode_steps`` counter) times its ``max_batch``."""
UNIT = "%"


def read(run):
    steps = run.counters1["engine"]["decode_steps"] - run.counters0["engine"]["decode_steps"]
    if steps <= 0:
        return None
    tokens = sum(max(len(s.text.split()) - 1, 0) for s in run.served
                 if s.status == "miss" and s.cost > 0)
    return 100.0 * tokens / (steps * run.counters1["engine_slots"])
