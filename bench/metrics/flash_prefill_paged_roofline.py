"""Share (%) of ``flash_prefill_paged``'s device time in the traced window
that the chip's roofline needs for the chunks prefilled there."""
from bench.measure import kernel_roofline, prefill_cost


def read(run):
    if run.trace is None:
        return None
    return kernel_roofline(run, "flash_prefill_paged", prefill_cost(run))
