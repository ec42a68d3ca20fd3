"""Tokens streamed inside the window to the sessions that arrived in it,
over the window's seconds: the warm-up's backlog does not count, and a
stall inside the window, a reply cut short or a turn not served lowers it."""


def read(run):
    n = sum(1 for r in run.requests if r.stream == "window"
            for t in r.token_times if run.t_open <= t < run.t_close)
    return n / run.seconds
