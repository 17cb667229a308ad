"""Distributed correctness on 8 fake host devices (subprocess-isolated so
the main test session keeps its single-device jax config)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.models.config import ModelConfig, TrainConfig
    from repro.train import step as TS
    from repro.launch.sharding import (batch_specs, state_specs,
                                       to_shardings)

    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = ModelConfig("t", 2, 64, 4, 2, 128, 256, head_dim=16)
    tc = TrainConfig(learning_rate=1e-3, n_microbatches=2)

    # --- sharded train step == single-device train step -------------------
    state = TS.init_state(jax.random.PRNGKey(0), cfg, tc)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32),
    }
    step = TS.build_train_step(cfg, tc)
    ref_state, ref_metrics = jax.jit(step)(state, batch)

    state_shape = jax.eval_shape(lambda: state)
    st_spec = state_specs(cfg, state_shape, mesh)
    b_spec = batch_specs(jax.eval_shape(lambda: batch), mesh)
    with jax.set_mesh(mesh):
        st_sh = jax.device_put(state, to_shardings(st_spec, mesh))
        b_sh = jax.device_put(batch, to_shardings(b_spec, mesh))
        jitted = jax.jit(step,
                         in_shardings=(to_shardings(st_spec, mesh),
                                       to_shardings(b_spec, mesh)))
        out_state, out_metrics = jitted(st_sh, b_sh)
    dl = abs(float(out_metrics["loss"]) - float(ref_metrics["loss"]))
    dp = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(ref_state["params"]),
        jax.tree.leaves(out_state["params"]), strict=True))
    # --- gradient compression under sharding -------------------------------
    tc2 = TrainConfig(learning_rate=1e-3, grad_compression="int8_ef")
    state2 = TS.init_state(jax.random.PRNGKey(0), cfg, tc2)
    step2 = TS.build_train_step(cfg, tc2)
    state2_shape = jax.eval_shape(lambda: state2)
    st2_spec = state_specs(cfg, state2_shape, mesh)
    with jax.set_mesh(mesh):
        st2_sh = jax.device_put(state2, to_shardings(st2_spec, mesh))
        jitted2 = jax.jit(step2,
                          in_shardings=(to_shardings(st2_spec, mesh),
                                        to_shardings(b_spec, mesh)))
        _s, m2 = jitted2(st2_sh, b_sh)
    print(json.dumps({
        "loss_delta": dl, "param_delta": dp,
        "compressed_loss_finite": bool(jnp.isfinite(m2["loss"])),
    }))
""")


@pytest.mark.slow
def test_sharded_train_step_matches_single_device(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=500,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["loss_delta"] < 1e-4, res
    assert res["param_delta"] < 1e-4, res
    assert res["compressed_loss_finite"]
