"""Benchmark driver: one module per paper table/figure, CSV to stdout.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run table23    # one artifact

Module list mirrors the paper (see DESIGN.md §7).  The classifier zoo is
trained once per seed and cached under experiments/bench_cache (delete to
retrain).  Scale knobs: REPRO_BENCH_{SEEDS,EPOCHS,SAMPLES}.
"""
import sys
import time
import traceback

from benchmarks import (fig3_splitting, fig4_params, fig5_histograms,
                        roofline, table1_models, table23_cascade,
                        table4_three_element, table5_hard_task,
                        table6_accuracy_effect, table7_llm_cascade)
from repro.launch.compile_cache import use_compile_cache

ARTIFACTS = {
    "table1": table1_models.main,
    "table23": table23_cascade.main,
    "table4": table4_three_element.main,
    "table5": table5_hard_task.main,
    "table6": table6_accuracy_effect.main,
    "table7_llm": table7_llm_cascade.main,
    "fig3": fig3_splitting.main,
    "fig4": fig4_params.main,
    "fig5": fig5_histograms.main,
    "roofline": roofline.main,
}


def main() -> None:
    names = sys.argv[1:] or list(ARTIFACTS)
    use_compile_cache()
    failures = []
    for name in names:
        print(f"\n# ===== {name} =====", flush=True)
        t0 = time.time()
        try:
            ARTIFACTS[name]()
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
    if failures:
        print(f"# FAILURES: {failures}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
