"""Model operations of the tokens decoded in the traced window over the
device time of the decode-step programs, as a share (%) of the bf16 peak."""
from bench.measure import decode_lengths, step_mfu, window_perf


def read(run):
    if run.trace is None:
        return None
    return step_mfu(run, "decode", run.family.decode_step(
        run.shapes, decode_lengths(run, *window_perf(run))))
