"""Model (``models/lm.py``), prompt side: the operations the prompts
admitted in the window needed (``bench/harness/flops.py``: each
suffix's matmuls, LoRA at its adapter's rank, causal attention over the
cached prefix and itself, the head at its last position), over the
device time of the engine's prefill programs in the trace, over the
chip's peak; a percentage."""
from bench.harness import flops, readers

PROGRAM = r"jit__prefill(_paged)?_fn$"


def read(run):
    if run.trace is None:
        return None
    work = sum(flops.prefill_flops(run.dims, s, L - s, r)
               for s, L, r in readers.prefills(run))
    return readers.share_of_peak(
        work, run.trace.seconds(PROGRAM, "modules"), run.peak)
