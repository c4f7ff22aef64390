"""Tokens of the backend's answers completed per second of the window: the
tokens of every window request that the engine answered by the window's
close, over the window's length. Above the knee the backlog never empties,
so this is the rate at which the engine serves its queue."""
UNIT = "tokens/s"


def read(run):
    close = run.t0 + run.seconds
    tokens = sum(len(s.text.split()) for s in run.served
                 if s.status == "miss" and s.cost > 0 and s.t_done <= close)
    return tokens / run.seconds if tokens else None
