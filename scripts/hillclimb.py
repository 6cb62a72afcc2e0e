import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimb driver: lower a (arch × shape) pair under named
variants and print the roofline terms side by side.

    PYTHONPATH=src python scripts/hillclimb.py kimi_train
    PYTHONPATH=src python scripts/hillclimb.py gemma_decode
    PYTHONPATH=src python scripts/hillclimb.py moe_group
"""
import dataclasses as dc
import sys

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch import steps as steps_lib
from repro.launch.dryrun import _terms, corrected_costs, lower_cfg
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.launch.shapes import SHAPES, input_specs
from repro.models import params as params_lib


def measure(cfg, shape_name, mesh, *, correct=True, microbatches=1,
            seq_over_model=False, chunked_ce=0, label=""):
    if chunked_ce:
        pshapes = params_lib.param_shapes(cfg, dtype=jnp.bfloat16, mesh=mesh)
        inputs = input_specs(cfg, shape_name, mesh, dtype=jnp.bfloat16)
        with jax.set_mesh(mesh):
            step, opt = steps_lib.make_train_step(cfg, chunked_ce=chunked_ce)
            osh = steps_lib.opt_state_shapes(opt, cfg, mesh)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                pshapes, osh, inputs)
        compiled = lowered.compile()
    elif microbatches > 1:
        # custom lowering with grad accumulation
        pshapes = params_lib.param_shapes(cfg, dtype=jnp.bfloat16, mesh=mesh)
        inputs = input_specs(cfg, shape_name, mesh, dtype=jnp.bfloat16)
        with jax.set_mesh(mesh):
            step, opt = steps_lib.make_train_step(cfg,
                                                  microbatches=microbatches)
            osh = steps_lib.opt_state_shapes(opt, cfg, mesh)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                pshapes, osh, inputs)
        compiled = lowered.compile()
    elif seq_over_model:
        pshapes = params_lib.param_shapes(cfg, dtype=jnp.bfloat16, mesh=mesh)
        inputs = input_specs(cfg, shape_name, mesh, dtype=jnp.bfloat16,
                             seq_over_model=True)
        with jax.set_mesh(mesh):
            serve_step = steps_lib.make_serve_step(cfg)
            lowered = jax.jit(serve_step, donate_argnums=(3,)).lower(
                pshapes, inputs["token"], inputs["pos"], inputs["cache"])
        compiled = lowered.compile()
    else:
        compiled = lower_cfg(cfg, shape_name, mesh).compile()
    mem = compiled.memory_analysis()
    if correct and cfg.num_periods > 2 and microbatches == 1 \
            and not chunked_ce:
        terms = corrected_costs(cfg, shape_name, mesh)
    else:
        terms = _terms(compiled)
    t_c = terms["flops"] / PEAK_FLOPS_BF16
    t_m = terms["bytes"] / HBM_BW
    t_x = terms["wire"] / ICI_BW
    print(f"  [{label}] compute={t_c*1e3:9.2f}ms memory={t_m*1e3:9.2f}ms "
          f"collective={t_x*1e3:9.2f}ms temp={mem.temp_size_in_bytes/1e9:7.1f}GB "
          f"args={mem.argument_size_in_bytes/1e9:6.1f}GB")
    return {"t_c": t_c, "t_m": t_m, "t_x": t_x,
            "temp_gb": mem.temp_size_in_bytes / 1e9, "terms": terms}


def kimi_train():
    """Pair 1 (worst memory / collective): kimi-k2 x train_4k.
    Lever A: gradient accumulation (microbatches)."""
    mesh = make_production_mesh()
    cfg = get_config("kimi-k2-1t-a32b")
    print("kimi-k2-1t-a32b x train_4k @16x16")
    measure(cfg, "train_4k", mesh, label="baseline")
    for mb in (4, 8):
        measure(cfg, "train_4k", mesh, microbatches=mb, label=f"mb={mb}")


def moe_group():
    """Pair 1 lever B: MoE dispatch group size (dispatch einsum FLOPs are
    linear in group size: 2·tokens·gs·k·cf·D)."""
    import repro.models.blocks as blocks
    mesh = make_production_mesh()
    cfg = get_config("kimi-k2-1t-a32b")
    print("kimi-k2 x train_4k: MOE_GROUP_SIZE sweep")
    for gs in (1024, 512, 256):
        blocks.MOE_GROUP_SIZE = gs
        measure(cfg, "train_4k", mesh, label=f"gs={gs}")
    blocks.MOE_GROUP_SIZE = 1024


def gemma_decode():
    """Pair 3 (paper-representative: the cascade's fast member serving):
    gemma3-1b x decode_32k.  Lever: int8 KV cache."""
    mesh = make_production_mesh()
    cfg = get_config("gemma3-1b")
    print("gemma3-1b x decode_32k @16x16")
    measure(cfg, "decode_32k", mesh, label="baseline bf16 cache")
    measure(dc.replace(cfg, kv_quant="int8"), "decode_32k", mesh,
            label="int8 KV cache")


def qwen_decode():
    """Pair 2: qwen2-vl-72b x decode_32k (biggest dense decode; its kv=8
    heads can't shard the 16-way model axis, so the cache replicates).
    Levers: shard cache seq over model; int8 KV cache; both."""
    mesh = make_production_mesh()
    cfg = get_config("qwen2-vl-72b")
    print("qwen2-vl-72b x decode_32k @16x16")
    measure(cfg, "decode_32k", mesh, label="baseline bf16 cache")
    measure(cfg, "decode_32k", mesh, seq_over_model=True,
            label="cache seq/model")
    measure(dc.replace(cfg, kv_quant="int8"), "decode_32k", mesh,
            label="int8 KV cache")
    measure(dc.replace(cfg, kv_quant="int8"), "decode_32k", mesh,
            seq_over_model=True, label="int8 + seq/model")


def chunked_ce():
    """Iteration 8: seq-chunked CE on the vocab-heavy archs — the logits
    [B,S,V] f32 transient should stop dominating temp memory.
    (cost terms not scan-corrected here; compare temp only)"""
    mesh = make_production_mesh()
    for arch in ("gemma3-1b", "phi4-mini-3.8b"):
        cfg = get_config(arch)
        print(f"{arch} x train_4k @16x16 (temp comparison)")
        measure(cfg, "train_4k", mesh, correct=False, label="baseline")
        measure(cfg, "train_4k", mesh, chunked_ce=512, label="chunked_ce=512")


def starcoder_train():
    """Pair 2 (most collective-bound: 6.5 TB/chip of all-gathers).
    Hypothesis: the T-sharded probs are all-gathered (9.7 GB x725)
    because v is not T-sharded; kv_seq_hint should turn the contraction
    into partial sums + a small out all-reduce."""
    mesh = make_production_mesh()
    cfg = get_config("starcoder2-7b")
    print("starcoder2-7b x train_4k @16x16")
    measure(cfg, "train_4k", mesh, label="baseline")
    measure(dc.replace(cfg, kv_seq_hint=True), "train_4k", mesh,
            label="kv_seq_hint")


def moonshot_train():
    """Pair 2 (collective-bound candidate): moonshot x train_4k.
    Lever: fsdp (2D weight sharding) on/off."""
    mesh = make_production_mesh()
    cfg = get_config("moonshot-v1-16b-a3b")
    print("moonshot-v1-16b-a3b x train_4k @16x16")
    measure(cfg, "train_4k", mesh, label="baseline (no fsdp)")
    measure(dc.replace(cfg, fsdp=True), "train_4k", mesh, label="fsdp=True")


EXPERIMENTS = {
    "kimi_train": kimi_train,
    "moe_group": moe_group,
    "gemma_decode": gemma_decode,
    "qwen_decode": qwen_decode,
    "starcoder_train": starcoder_train,
    "chunked_ce": chunked_ce,
    "moonshot_train": moonshot_train,
}

if __name__ == "__main__":
    for name in sys.argv[1:] or list(EXPERIMENTS):
        EXPERIMENTS[name]()
