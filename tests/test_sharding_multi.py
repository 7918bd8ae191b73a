"""Multi-device sharding correctness: run a REAL sharded train step on 8
forced host devices, and on 4 as a (data, model) mesh like one four-chip
host (subprocess — device count must be set before jax init), and compare
against the single-device result.  Also covers the cell builder
and divisibility-aware specs on a small mesh."""

import json
import math
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[7:])


SHARDED_VS_SINGLE = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import reduced_config
    from repro.models import ModelOptions
    from repro.train import TrainConfig, init_train_state, make_train_step, \\
        train_state_specs, batch_sharding
    from repro.sharding.ctx import activation_rules
    from repro.data import StreamSource

    cfg = reduced_config("qwen3-14b")
    opts = ModelOptions(compute_dtype="float32")
    tcfg = TrainConfig(remat=False)
    src = StreamSource(vocab_size=cfg.vocab_size, batch=8, seq_len=32, seed=0)
    batch = src.batch_at(0)

    # single device reference
    state = init_train_state(jax.random.key(0), cfg, tcfg)
    step1 = jax.jit(make_train_step(cfg, tcfg, opts))
    s1, m1 = step1(state, batch)
    s1, m1 = step1(s1, src.batch_at(1))
    ref_loss = float(m1["loss"])

    mesh = jax.make_mesh(MESH_SHAPE, MESH_AXES)
    rules = activation_rules()
    state2 = init_train_state(jax.random.key(0), cfg, tcfg)
    specs = train_state_specs(state2, mesh)
    state2 = jax.device_put(state2, specs)
    bspecs = batch_sharding(mesh, batch)
    step2 = jax.jit(make_train_step(cfg, tcfg, opts, mesh=mesh, act_rules=rules),
                    in_shardings=(specs, bspecs), donate_argnums=0)
    s2, _ = step2(state2, jax.device_put(batch, bspecs))
    s2, m2 = step2(s2, jax.device_put(src.batch_at(1), bspecs))
    sh_loss = float(m2["loss"])

    # parameter agreement after 2 steps
    import jax.tree_util as jtu
    diffs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - np.asarray(b, np.float32))))
             for a, b in zip(jtu.tree_leaves(s1["params"]), jtu.tree_leaves(s2["params"]))]
    print("RESULT " + json.dumps({"ref_loss": ref_loss, "sh_loss": sh_loss,
                                  "max_param_diff": max(diffs)}))
""")


def check_sharded_vs_single(shape, axes):
    code = (SHARDED_VS_SINGLE.replace("MESH_SHAPE", repr(shape))
            .replace("MESH_AXES", repr(axes)))
    r = run_py(code, devices=math.prod(shape))
    assert abs(r["ref_loss"] - r["sh_loss"]) < 1e-3, r
    assert r["max_param_diff"] < 1e-4, r


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    check_sharded_vs_single((2, 2, 2), ("pod", "data", "model"))


@pytest.mark.slow
def test_sharded_train_step_on_four_device_data_model_mesh():
    """The mesh of one four-chip host: no ``pod`` axis."""
    check_sharded_vs_single((2, 2), ("data", "model"))


COMPRESSED_GRADS = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    from repro.configs import reduced_config
    from repro.models import ModelOptions
    from repro.train import TrainConfig, init_train_state, make_train_step, \\
        train_state_specs, batch_sharding
    from repro.sharding.ctx import activation_rules
    from repro.data import StreamSource

    cfg = reduced_config("gemma-2b")
    opts = ModelOptions(compute_dtype="float32")
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    rules = activation_rules()
    src = StreamSource(vocab_size=cfg.vocab_size, batch=8, seq_len=32, seed=0)
    batch = src.batch_at(0)

    tc_base = TrainConfig(remat=False)
    st = init_train_state(jax.random.key(0), cfg, tc_base)
    sp = train_state_specs(st, mesh)
    bspecs = batch_sharding(mesh, batch)
    base_step = jax.jit(make_train_step(cfg, tc_base, opts, mesh=mesh, act_rules=rules),
                        in_shardings=(sp, bspecs))
    _, mb = base_step(jax.device_put(st, sp), jax.device_put(batch, bspecs))

    tc_c = TrainConfig(remat=False, compress_pod_grads=True, num_pods=2)
    st_c = init_train_state(jax.random.key(0), cfg, tc_c)
    sp_c = train_state_specs(st_c, mesh)
    c_step = jax.jit(make_train_step(cfg, tc_c, opts, mesh=mesh, act_rules=rules),
                     in_shardings=(sp_c, bspecs))
    _, mc = c_step(jax.device_put(st_c, sp_c), jax.device_put(batch, bspecs))
    print("RESULT " + json.dumps({"base_loss": float(mb["loss"]),
                                  "comp_loss": float(mc["loss"]),
                                  "base_gnorm": float(mb["grad_norm"]),
                                  "comp_gnorm": float(mc["grad_norm"])}))
""")


@pytest.mark.slow
def test_compressed_pod_gradients_close_to_exact():
    r = run_py(COMPRESSED_GRADS)
    assert abs(r["base_loss"] - r["comp_loss"]) < 1e-2, r
    # int8 quantization perturbs the gradient slightly but not wildly
    assert abs(r["base_gnorm"] - r["comp_gnorm"]) / max(r["base_gnorm"], 1e-9) < 0.1, r


CELL_BUILD = textwrap.dedent("""
    import json
    import jax
    from repro.launch.cells import build_cell, lower_cell, CellOptions
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    # shrink shapes via the production builder on a smoke mesh is not
    # supported (shapes are fixed); instead check spec construction only.
    cell = build_cell("gemma-2b", "decode_32k", mesh, CellOptions())
    kinds = {type(s).__name__ for s in jax.tree.leaves(cell.in_shardings)}
    print("RESULT " + json.dumps({"kind": cell.kind, "n_args": len(cell.args),
                                  "sharding_types": sorted(kinds)}))
""")


def test_cell_builder_on_small_mesh():
    r = run_py(CELL_BUILD)
    assert r["kind"] == "decode" and r["n_args"] == 3
    assert r["sharding_types"] == ["NamedSharding"]
