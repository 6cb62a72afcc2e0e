"""The control of a cell's correctness check, on the chip.

    python3 bench/control.py --workload stage0.chat --seconds 20 --seeds 11 12 13

For each seed: one run of the cell at its own size and load (the window
as ``run.py`` serves it, with the program's readings printed as usual),
then the same sample of served requests scored with the fp8 reference in
the program's place.  The control has to come out as not correct: its
readings set the upper end of each limit.  The benchmark's own runs do
not run this.  Prints one ``control`` JSON line per seed.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import cells, compare, main  # noqa: E402


def control(workload: str, seconds: float, seeds) -> int:
    cell = cells.resolve(workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: no TPU", file=sys.stderr)
        return 2
    t0 = T0
    for seed in seeds:
        keep = {}
        prog = main.run(cell, seed, seconds, False, t0, keep=keep)
        low = compare.control_readings(keep["picked"], keep["params"],
                                       keep["cfgs"])
        print("control " + json.dumps({
            "seed": seed, "program": {k: prog["compared"][k]["value"]
                                      for k in compare.NUMBERS},
            "control": low, "limits": cell.settings["limits"],
            "control_fails": not compare.judge(low, cell.settings["limits"])}),
            flush=True)
        keep.clear()
        gc.collect()
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    sys.exit(control(a.workload, a.seconds, a.seeds))
