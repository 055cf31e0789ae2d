"""Distributed correctness on an 8-device CPU mesh (run in a subprocess so
the main pytest process keeps 1 device)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.distributed.ctx import mesh_context
    from repro.distributed.sharding import (batch_specs, param_specs,
                                            sanitize_specs, to_named)
    from repro.launch.mesh import make_test_mesh
    from repro.models.config import ShapeSpec
    from repro.models.model import Model
    from repro.training.train_step import init_train_state, make_train_step

    assert len(jax.devices()) == 8
    cfg = get_smoke_config("granite-3-8b").scaled(param_dtype="float32")
    model = Model(cfg, attn_chunk=16, remat=False)
    B, S = 8, 32
    shape = ShapeSpec("t", S, B, "train")
    rngb = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rngb.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32),
             "targets": jnp.asarray(rngb.integers(0, cfg.vocab_size, (B, S)),
                                    jnp.int32)}

    # single-device reference
    state = init_train_state(model, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model))
    _, m_ref = step(state, batch)
    ref_loss = float(m_ref["loss"])

    # sharded on a 2x4 mesh
    mesh = make_test_mesh(2, 4)
    pspec = sanitize_specs(state["params"],
                           param_specs(cfg, state["params"], "train"), mesh)
    state_spec = {"params": pspec, "m": pspec, "v": pspec,
                  "step": jax.sharding.PartitionSpec()}
    bspec = sanitize_specs(batch, batch_specs(cfg, shape, mesh), mesh)
    with mesh_context(mesh):
        jstep = jax.jit(make_train_step(model),
                        in_shardings=(to_named(mesh, state_spec),
                                      to_named(mesh, bspec)),
                        out_shardings=(to_named(mesh, state_spec), None))
        sh_state = jax.device_put(state, to_named(mesh, state_spec))
        sh_batch = jax.device_put(batch, to_named(mesh, bspec))
        new_state, m_sh = jstep(sh_state, sh_batch)
        sh_loss = float(m_sh["loss"])
        # one more step to ensure the updated sharded state is usable
        _, m2 = jstep(new_state, sh_batch)

    # serving path on the mesh
    psspec = sanitize_specs(state["params"],
                            param_specs(cfg, state["params"], "serving"), mesh)
    with mesh_context(mesh):
        jpre = jax.jit(model.prefill,
                       in_shardings=(to_named(mesh, psspec), None))
        logits, cache = jpre(jax.device_put(state["params"],
                                            to_named(mesh, psspec)),
                             {"tokens": batch["tokens"]})
    l_ref, _ = model.prefill(state["params"], {"tokens": batch["tokens"]})
    prefill_err = float(jnp.abs(logits - l_ref).max())

    print(json.dumps({"ref_loss": ref_loss, "sharded_loss": sh_loss,
                      "loss2": float(m2["loss"]),
                      "prefill_err": prefill_err}))
""")


@pytest.mark.slow
def test_sharded_train_matches_single_device():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu",
                       "PYTHONPATH": str(REPO / "src")},
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert abs(out["ref_loss"] - out["sharded_loss"]) < 5e-3, out
    assert out["loss2"] < out["ref_loss"] + 1.0
    assert out["prefill_err"] < 5e-2, out
