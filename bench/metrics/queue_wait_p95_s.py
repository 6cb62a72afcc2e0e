"""Scheduler: 95th percentile of arrival -> first admission to a KV row,
over the requests that arrived in the window (never admitted: inf)."""
import math


def read(run):
    waits = [r.admit_times[0] - r.arrival_time if r.admit_times else math.inf
             for r in run.requests]
    return run.percentile(waits, 95) if waits else None
