"""Every token streamed inside the window, by any request, over the window's
seconds."""


def read(run):
    n = sum(1 for r in run.requests for t in r.token_times
            if run.t_open <= t < run.t_close)
    return n / (run.t_close - run.t_open)
