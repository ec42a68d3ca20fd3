"""Peak share (%) of the pool's pages held by live requests, sampled after
every engine step in the window."""


def read(run):
    return 100.0 * run.pages_peak / run.pool_pages if run.pool_pages else None
