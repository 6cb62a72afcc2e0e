"""Drive the engine through one run: warm-up traffic, the measured
window, and (for cells judged on tails) the drain of the window's
requests.

The loop is the engine's own ``run()`` loop (step while work is live or
admissible, else sleep until the next arrival), cut at the window's
edges.  At each edge it snapshots the program's counters, so every
number of the window is a difference over exactly the window's ticks.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np


@dataclass
class Snapshot:
    t: float                        # engine clock
    wall: float                     # time.perf_counter()
    tick: int
    live_tokens: List[int]
    processed_tokens: List[int]
    progress: dict                  # (rid, tier) -> (positions, emitted)


@dataclass
class Window:
    start: float                    # engine clock of the window's edges
    stop: float
    first: Optional[Snapshot] = None
    last: Optional[Snapshot] = None
    requests: list = field(default_factory=list)   # arrived in the window
    done_in_window: list = field(default_factory=list)
    drained: bool = True
    compiles_in_window: int = 0
    first_rid: int = 0              # requests of earlier batches are not ours


def progress(engine) -> dict:
    """Positions each (request, tier) has run through so far, and how
    many of them emitted a token: a finished tier ran its prompt and all
    but its last answer token; a live row, what it has written."""
    out = {}
    last = len(engine.tiers) - 1
    for req in engine.requests:
        for t, toks in enumerate(req.tokens_by_tier):
            out[(req.rid, t)] = (req.prompt_tokens + len(toks) - 1, len(toks))
        if req.state.name in ("PREFILL", "DECODE") and req.tier <= last:
            rt = engine.runtimes[req.tier]
            slot = req.slot
            n = len(req.tokens)
            written = (int(rt.prefill_pos[slot]) if n == 0
                       else req.prompt_tokens + n - 1)
            out[(req.rid, req.tier)] = (written, n)
    return out


def snapshot(engine) -> Snapshot:
    m = engine.metrics
    return Snapshot(engine.clock.now(), time.perf_counter(), engine.tick_id,
                    list(m.step_live_tokens), list(m.step_processed_tokens),
                    progress(engine))


def serve(engine, window: Window, *, drain_s: float, until_drained: bool,
          on_edge: Callable[[str], None] = lambda edge: None,
          compile_count: Callable[[], int] = lambda: 0) -> Window:
    """Step the engine from its clock's zero through the window.  With
    ``until_drained`` it goes on until every request that arrived in
    the window has ended, or ``drain_s`` past the window.  ``on_edge``
    hears ``start`` and ``stop``, each after the edge's snapshot."""
    clock, sched = engine.clock, engine.scheduler
    ours = [r for r in engine.requests if r.rid >= window.first_rid]
    window.requests = [r for r in ours
                       if window.start <= r.arrival_time < window.stop]
    ntiers = len(engine.tiers)
    compiles0 = None

    def edge(name):
        nonlocal compiles0
        snap = snapshot(engine)
        if name == "start":
            window.first = snap
            compiles0 = compile_count()
        else:
            window.last = snap
            window.compiles_in_window = compile_count() - compiles0
        on_edge(name)

    while True:
        now = clock.now()
        if window.first is None and now >= window.start:
            edge("start")
        if window.last is None and now >= window.stop:
            edge("stop")
        if window.last is not None:
            if not until_drained:
                break
            pending = [r for r in window.requests
                       if r.state.name not in ("DONE", "SHED", "FAILED")]
            if not pending:
                break
            if now >= window.stop + drain_s:
                window.drained = False
                break
        if not engine._any_occupied() and not any(
                sched.admissible(t, now) for t in range(ntiers)):
            if not sched.pending:
                nxt = window.stop if window.last is None else now + 0.05
            else:
                nxt = min(sched.queues[0][0].arrival_time,
                          window.stop if window.last is None else np.inf)
                if window.first is None:
                    nxt = min(nxt, window.start)
            clock.wait_until(nxt)
            continue
        engine.step(clock.now())
        clock.step_done()
    window.done_in_window = [
        r for r in ours if r.state.name == "DONE"
        and window.start <= r.finish_time < window.stop]
    return window
