"""Device: the share of the traced window in which no operation ran on
the device, mean over the cell's chips, in percent."""


def read(run):
    return run.idle_share()
