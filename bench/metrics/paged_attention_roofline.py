"""Kernels (``kernels/paged_attention.py``): the least time the chip
needs for the decode attention of the tokens decoded in the window
(K and V pages up to each row's actual length, its query and output;
``bench/harness/flops.py``), over the kernel's summed device time in the
trace; a percentage. Almost all of it is bound by bandwidth."""
from bench.harness import flops, readers

KERNEL = r"%paged_attention$"


def read(run):
    if run.trace is None:
        return None
    secs = run.trace.seconds(KERNEL)
    if secs <= 0:
        return None
    f = b = 0
    for n, _ in readers.decoded_tokens(run):
        df, db = flops.paged_attention_cost(run.dims, n)
        f, b = f + df, b + db
    least, _ = flops.roofline_seconds(f, b, run.peak)
    return 100.0 * least / secs
