"""Share (%) of ``paged_decode``'s device time in the traced window that the
chip's roofline needs for the keys and values its tokens attend to."""
from bench.measure import decode_cost, kernel_roofline


def read(run):
    if run.trace is None:
        return None
    return kernel_roofline(run, "paged_decode", decode_cost(run))
