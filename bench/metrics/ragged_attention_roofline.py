"""Kernels: the least device time of the window's ``ragged_attention``
calls over the time they took, in percent.  Per launch the larger of
K and V of ``kv_read`` positions plus q and o of ``tokens`` over HBM
bandwidth, and ``4 * heads * head_dim * kv_pairs`` FLOPs over the bf16
peak, in every attention layer (``bench/harness/roofline.py``)."""
from bench.harness import roofline


def read(run):
    return roofline.share(run, roofline.attention_bound_s,
                          run.trace.kernel_s.get("ragged_attention"))
