"""Scheduler: share of the window's requests that the cheap tier's gate
sent on to the expensive tier, in percent of those it gated."""


def read(run):
    gated = [r for r in run.requests if r.seq_conf_by_tier]
    if not gated:
        return None
    return 100.0 * sum(r.tier > 0 for r in gated) / len(gated)
