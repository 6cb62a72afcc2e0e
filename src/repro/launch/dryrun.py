import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, with no real allocation (ShapeDtypeStruct inputs).

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch gemma3-1b --shape train_4k [--multi-pod] [--out DIR]

Success criteria (deliverable e): ``.lower().compile()`` succeeds for the
(16,16) single-pod mesh and the (2,16,16) multi-pod mesh for every pair;
memory_analysis / cost_analysis / collective schedule recorded for
EXPERIMENTS.md §Dry-run and §Roofline.

The XLA_FLAGS line above MUST run before any other jax-touching import —
jax locks the device count on first init.  This file is the only place the
512-device platform is forced; tests and benches see the real device.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED, get_config, long_context_variant
from repro.launch import roofline as roofline_lib
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh, num_chips
from repro.launch.shapes import SHAPES, input_specs
from repro.models import params as params_lib


def config_for(arch: str, shape_name: str):
    """Resolve the config (long_500k uses the documented sliding-window
    variant for full-attention archs; see DESIGN.md)."""
    cfg = get_config(arch)
    note = ""
    if shape_name == "long_500k" and not cfg.supports_long_natively:
        cfg = long_context_variant(cfg)
        note = f"sliding-window variant (w={cfg.long_variant_window})"
    return cfg, note


def lower_cfg(cfg, shape_name: str, mesh, *, dtype=jnp.bfloat16,
              donate: bool = True):
    """Lower one step function for a concrete config."""
    shape = SHAPES[shape_name]
    pshapes = params_lib.param_shapes(cfg, dtype=dtype, mesh=mesh)
    inputs = input_specs(cfg, shape_name, mesh, dtype=dtype)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            train_step, opt = steps_lib.make_train_step(cfg)
            oshapes = steps_lib.opt_state_shapes(opt, cfg, mesh, dtype=jnp.float32)
            fn = jax.jit(train_step,
                         donate_argnums=(0, 1) if donate else ())
            lowered = fn.lower(pshapes, oshapes, inputs)
        elif shape.kind == "prefill":
            prefill_step = steps_lib.make_prefill_step(cfg)
            lowered = jax.jit(prefill_step).lower(pshapes, inputs)
        else:
            serve_step = steps_lib.make_serve_step(cfg)
            fn = jax.jit(serve_step, donate_argnums=(3,) if donate else ())
            lowered = fn.lower(pshapes, inputs["token"], inputs["pos"],
                               inputs["cache"])
    return lowered


def _terms(compiled):
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    from repro.launch.hlo import collective_stats
    st = collective_stats(hlo)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "wire": st.total_wire_bytes,
            "bytes_by_op": st.bytes_by_op,
            "count_by_op": st.count_by_op}


def corrected_costs(cfg, shape_name, mesh):
    """XLA cost_analysis counts while-loop (scan) bodies ONCE regardless of
    trip count (measured: scan of P matmuls reports 1/P of the unrolled
    FLOPs).  Correction: lower unrolled 1- and 2-period variants — both
    exact — and extrapolate linearly:

        per_period = T(2) - T(1);  T(P) = T(1) + (P-1) * per_period

    Exact because all periods are structurally identical.
    """
    import dataclasses as dc
    c1 = dc.replace(cfg, num_periods=1, unroll_periods=True)
    c2 = dc.replace(cfg, num_periods=2, unroll_periods=True)
    t1 = _terms(lower_cfg(c1, shape_name, mesh).compile())
    t2 = _terms(lower_cfg(c2, shape_name, mesh).compile())
    P = cfg.num_periods
    out = {}
    for k in ("flops", "bytes", "wire"):
        body = max(t2[k] - t1[k], 0.0)
        out[k] = t1[k] + (P - 1) * body
    # collective op counts, linearly extrapolated for the record
    out["bytes_by_op"] = {k: t1["bytes_by_op"].get(k, 0.0)
                          + (P - 1) * max(t2["bytes_by_op"].get(k, 0.0)
                                          - t1["bytes_by_op"].get(k, 0.0), 0.0)
                          for k in set(t1["bytes_by_op"]) | set(t2["bytes_by_op"])}
    out["count_by_op"] = {k: t1["count_by_op"].get(k, 0)
                          + (P - 1) * max(t2["count_by_op"].get(k, 0)
                                          - t1["count_by_op"].get(k, 0), 0)
                          for k in set(t1["count_by_op"]) | set(t2["count_by_op"])}
    return out


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             out_dir: str = None, verbose: bool = True, correct: bool = True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cfg, note = config_for(arch, shape_name)
    t0 = time.time()
    lowered = lower_cfg(cfg, shape_name, mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    shape = SHAPES[shape_name]
    # The roofline table is single-pod only (the multi-pod pass proves the
    # pod axis shards) — skip the 3-compile scan-cost correction there.
    if correct and cfg.num_periods > 2 and not multi_pod:
        terms = corrected_costs(cfg, shape_name, mesh)
        note = (note + "; " if note else "") + "scan-cost corrected"
    else:
        terms = _terms(compiled)
        if multi_pod:
            note = (note + "; " if note else "") + \
                "raw scan-counted costs (roofline is single-pod)"
    cost = {"flops": terms["flops"], "bytes accessed": terms["bytes"]}
    rl = roofline_lib.analyze(
        arch=arch, shape=shape_name, mesh_name=mesh_name,
        chips=num_chips(mesh), cost=cost, hlo_text="",
        model_flops=roofline_lib.model_flops_for(cfg, shape, shape.kind),
        memory_stats=mem, note=note)
    # overwrite collective fields with the corrected parse
    rl.collective_detail["bytes_by_op"] = terms["bytes_by_op"]
    rl.collective_detail["count_by_op"] = terms["count_by_op"]
    from repro.launch.mesh import ICI_BW
    rl = dataclasses.replace(
        rl, wire_bytes_per_chip=terms["wire"],
        t_collective=terms["wire"] / ICI_BW)
    terms_d = {"compute": rl.t_compute, "memory": rl.t_memory,
               "collective": rl.t_collective}
    rl = dataclasses.replace(rl, bottleneck=max(terms_d, key=terms_d.get))

    if verbose:
        print(f"[{arch} x {shape_name} @ {mesh_name}] "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops/chip={rl.flops_per_chip:.3e} "
              f"bytes/chip={rl.bytes_per_chip:.3e}")
        print(f"  collectives: {rl.collective_detail['count_by_op']} "
              f"wire_bytes/chip={rl.wire_bytes_per_chip:.3e}")
        print(f"  roofline: compute={rl.t_compute*1e3:.2f}ms "
              f"memory={rl.t_memory*1e3:.2f}ms "
              f"collective={rl.t_collective*1e3:.2f}ms "
              f"-> {rl.bottleneck}-bound "
              f"(useful-flops {rl.useful_flops_ratio:.2f})")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        rec = dataclasses.asdict(rl)
        rec["lower_s"] = t_lower
        rec["compile_s"] = t_compile
        rec["memory_analysis"] = repr(mem)
        path = os.path.join(out_dir,
                            f"{arch}__{shape_name}__{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rl


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="architecture id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="input shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the (2,16,16) 512-chip mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun",
                    help="JSON output dir")
    ap.add_argument("--keep-going", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="skip pairs whose JSON already exists in --out")
    args = ap.parse_args()

    archs = ASSIGNED if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                if args.resume and os.path.exists(os.path.join(
                        args.out, f"{arch}__{shape}__{mesh_name}.json")):
                    print(f"skip [{arch} x {shape} @ {mesh_name}] (exists)")
                    continue
                try:
                    run_pair(arch, shape, multi_pod=mp, out_dir=args.out)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"FAIL [{arch} x {shape} multi_pod={mp}]: {e}")
                    if not args.keep_going:
                        traceback.print_exc()
                        raise SystemExit(1)
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nAll dry-runs passed.")


if __name__ == "__main__":
    main()
