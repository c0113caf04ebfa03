"""Requests completed inside the window, over the window's seconds."""


def read(run):
    n = sum(1 for r in run.requests if r.done_t is not None
            and r.status == "completed" and r.done_t < run.t_close)
    return n / run.seconds
