"""Kernels: device time of ``ragged_attention`` over device busy time in
the traced window, in percent."""


def read(run):
    return run.kernel_share("ragged_attention")
