"""Small-mesh dry-run integration tests.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process keeps its single real device (the production
512-device forcing lives only in repro.launch.dryrun).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=540):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.mark.parametrize("shape_name,arch", [
    ("train_4k", "gemma3-1b"),
    ("decode_32k", "rwkv6-3b"),
    ("prefill_32k", "granite-moe-3b-a800m"),
])
def test_small_mesh_lower_compile(shape_name, arch):
    """Lower+compile a REDUCED config on a 2x4 mesh: proves the sharding
    rules produce a coherent GSPMD program end to end."""
    out = _run(f"""
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config, smoke_variant
        from repro.launch.mesh import make_test_mesh
        from repro.launch.shapes import SHAPES, input_specs
        from repro.launch import steps as steps_lib
        from repro.models import params as params_lib

        mesh = make_test_mesh(8)
        cfg = get_config("{arch}", "smoke")
        # reduced shape in the same kind as {shape_name}
        import repro.launch.shapes as shp
        kind = SHAPES["{shape_name}"].kind
        shp.SHAPES["tiny"] = shp.InputShape("tiny", 64, 8, kind)
        pshapes = params_lib.param_shapes(cfg, dtype=jnp.float32, mesh=mesh)
        inputs = input_specs(cfg, "tiny", mesh, dtype=jnp.float32)
        with jax.set_mesh(mesh):
            if kind == "train":
                step, opt = steps_lib.make_train_step(cfg)
                osh = steps_lib.opt_state_shapes(opt, cfg, mesh)
                lowered = jax.jit(step).lower(pshapes, osh, inputs)
            elif kind == "prefill":
                lowered = jax.jit(steps_lib.make_prefill_step(cfg)).lower(pshapes, inputs)
            else:
                lowered = jax.jit(steps_lib.make_serve_step(cfg)).lower(
                    pshapes, inputs["token"], inputs["pos"], inputs["cache"])
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        assert cost.get("flops", 0) > 0
        print("OK", compiled.memory_analysis().argument_size_in_bytes)
    """)
    assert "OK" in out


def test_small_mesh_real_train_step_runs():
    """Actually execute a sharded train step on 8 host devices and check
    loss finiteness — beyond lowering, the program runs."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.launch.mesh import make_test_mesh
        from repro.launch import steps as steps_lib
        from repro.models import init_params, params as params_lib
        from repro.data import shard_batch

        mesh = make_test_mesh(8)
        cfg = get_config("granite-moe-3b-a800m", "smoke")
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key, jnp.float32)
        specs = params_lib.param_specs(cfg, mesh)
        params = jax.tree.map(
            lambda a, s: jax.device_put(a, jax.sharding.NamedSharding(mesh, s)),
            params, specs, is_leaf=lambda x: hasattr(x, 'shape') and not isinstance(x, dict))
        step, opt = steps_lib.make_train_step(cfg, lr=1e-2)
        state = opt.init(params)
        batch = {"tokens": np.random.randint(0, cfg.vocab_size, (8, 64)).astype(np.int32)}
        batch = shard_batch(batch, mesh)
        with jax.set_mesh(mesh):
            params, state, m = jax.jit(step)(params, state, batch)
        loss = float(m["loss"])
        assert np.isfinite(loss), loss
        print("OK loss", loss)
    """)
    assert "OK loss" in out


def test_collective_parser_sees_collectives():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_test_mesh
        from repro.launch.hlo import collective_stats

        mesh = make_test_mesh(8)
        w = jax.ShapeDtypeStruct((256, 256), jnp.float32,
                                 sharding=NamedSharding(mesh, P(None, "model")))
        x = jax.ShapeDtypeStruct((16, 256), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data", None)))
        f = lambda w, x: jnp.sum((x @ w) ** 2)
        compiled = jax.jit(f).lower(w, x).compile()
        st = collective_stats(compiled.as_text())
        assert st.total_raw_bytes > 0, st
        assert "all-reduce" in st.bytes_by_op
        print("OK", st.bytes_by_op)
    """)
    assert "OK" in out
