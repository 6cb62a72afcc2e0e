"""Kernels: the least device time of the window's ``ragged_attention``
calls over the time they took, in percent.  Per launch the larger of
the bytes the kernel moves (the cache of ``kv_read`` positions, the
queries and outputs of ``tokens``) over HBM bandwidth, and the FLOPs of
``kv_pairs`` query-key pairs over the bf16 peak, in every attention
layer, as the tier's architecture counts them
(``bench/harness/roofline.py``, ``bench/harness/arch/``)."""
from bench.harness import roofline


def read(run):
    return roofline.share(run, roofline.attention_bound_s,
                          run.trace.kernel_s.get("ragged_attention"))
