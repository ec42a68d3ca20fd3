"""Model operations of the prompt tokens computed in the traced window over
the device time of the chunk-step programs, as a share (%) of the bf16
peak."""
from bench import flops
from bench.measure import prefill_chunks, step_mfu, window_perf


def read(run):
    if run.trace is None:
        return None
    return step_mfu(run, "chunk", flops.chunk_step(
        run.shapes, prefill_chunks(run, *window_perf(run))))
