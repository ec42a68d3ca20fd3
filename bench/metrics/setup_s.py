"""Process start to the window opening: weights, engine, compilation, the
shared system prompt and the warm-up traffic (s)."""


def read(run):
    return run.setup_s
