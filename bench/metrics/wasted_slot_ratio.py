"""Model step: token slots the window's compiled steps processed that
held no live token (bucket padding), in percent of all processed."""


def read(run):
    live, processed = run.token_slots()
    return 100.0 * (processed - live) / processed if processed else None
