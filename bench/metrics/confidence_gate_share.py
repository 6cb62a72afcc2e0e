"""Kernels: device time of ``confidence_gate`` over device busy time in
the traced window, in percent."""


def read(run):
    return run.kernel_share("confidence_gate")
