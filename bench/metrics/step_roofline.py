"""Model step: the least device time of the window's launches over the
device's busy time, in percent.  Per launch the larger of the tier's
weights plus the KV read and written over HBM bandwidth, and the matrix,
attention and output-projection FLOPs over the bf16 peak
(``bench/harness/roofline.py``)."""
from bench.harness import roofline


def read(run):
    return roofline.share(run, roofline.step_bound_s, run.trace.busy_s)
