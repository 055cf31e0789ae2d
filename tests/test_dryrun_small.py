"""Dry-run machinery on a small 8-device mesh (subprocess; fast)."""
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
    import jax
    from repro.configs import get_smoke_config
    from repro.distributed.ctx import mesh_context
    from repro.distributed.sharding import (batch_specs, cache_specs,
                                            param_specs, sanitize_specs,
                                            to_named)
    from repro.launch.dryrun import parse_collectives
    from repro.models.config import ShapeSpec
    from repro.models.model import Model

    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh(2, 4)
    cfg = get_smoke_config("granite-3-8b")
    model = Model(cfg, attn_chunk=16, remat=False)
    shape = ShapeSpec("d", 64, 8, "decode")
    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspec = sanitize_specs(params_shape,
                           param_specs(cfg, params_shape, "serving"), mesh)
    ins = model.input_specs(shape)
    cspec = sanitize_specs(ins["cache"], cache_specs(cfg, shape, mesh), mesh)
    with mesh_context(mesh):
        lowered = jax.jit(model.decode_step,
                          in_shardings=(to_named(mesh, pspec),
                                        to_named(mesh, cspec), None)
                          ).lower(params_shape, ins["cache"], ins["tokens"])
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    coll = parse_collectives(compiled.as_text(), {"body": cfg.num_layers})
    print(json.dumps({
        "flops": float(cost.get("flops", 0)),
        "temp": int(getattr(mem, "temp_size_in_bytes", 0)),
        "coll_bytes": coll["per_device_bytes"],
        "n_coll": sum(coll["counts"].values()),
    }))
""")


@pytest.mark.slow
def test_dryrun_small_mesh_compiles_and_analyzes():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu",
                       "PYTHONPATH": str(REPO / "src")},
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["flops"] > 0
    assert out["n_coll"] > 0        # TP decode must communicate
