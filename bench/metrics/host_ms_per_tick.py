"""Engine tick, host: the window's engine-tick time outside the blocking
device fetches, per tick, in ms (the Tracer's ``tick`` spans minus the
``device_get`` spans inside them)."""


def read(run):
    ticks = run.phase_spans("tick")
    if not ticks:
        return None
    gets = sum(dur for _, dur in run.phase_spans("device_get"))
    return (sum(dur for _, dur in ticks) - gets) / len(ticks) / 1e3
