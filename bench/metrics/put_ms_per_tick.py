"""Engine tick, host: the window's host->device puts of the launches'
plans (the Tracer's ``put`` spans) per engine tick, in ms."""


def read(run):
    ticks = sum(e["name"] == "tick" for e in run.phases)
    puts = [e["dur"] for e in run.phases if e["name"] == "put"]
    if not ticks or not puts:
        return None
    return sum(puts) / ticks / 1e3
