"""Kernels (``kernels/bgmv.py``): the least time the chip needs for the
decode LoRA of the tokens decoded in the window, with each row's A and
B counted at its adapter's true rank and not the arena's padded
``r_max`` (``bench/harness/flops.py``), over the kernel's summed device
time in the trace; a percentage."""
from bench.harness import flops, readers

KERNEL = r"%bgmv$"


def read(run):
    if run.trace is None:
        return None
    secs = run.trace.seconds(KERNEL)
    if secs <= 0:
        return None
    f = b = 0
    for _, rank in readers.decoded_tokens(run):
        df, db = flops.bgmv_cost(run.dims, rank)
        f, b = f + df, b + db
    least, _ = flops.roofline_seconds(f, b, run.peak)
    return 100.0 * least / secs
