"""Model (``models/lm.py``), whole decode step: the operations the
tokens decoded in the window needed (all weights, LoRA at each row's
rank, attention over each row's actual context), over the device time
of the engine's fused decode programs in the trace, over the chip's
peak; a percentage."""
from bench.harness import flops, readers

PROGRAM = r"jit__fused(_paged)?_fn$"


def read(run):
    if run.trace is None:
        return None
    work = sum(flops.decode_flops(run.dims, n, r)
               for n, r in readers.decoded_tokens(run))
    return readers.share_of_peak(
        work, run.trace.seconds(PROGRAM, "modules"), run.peak)
