"""Dry-run machinery smoke test on 8 fake devices (subprocess so the main
test process keeps its single-device view)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax, jax.numpy as jnp
from repro import configs as cfgs
from repro.launch import mesh as mesh_lib
from repro.models import api
from repro.optim import get_optimizer

mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = cfgs.get_smoke("llama3-8b")
cfg = dataclasses.replace(cfg, n_kv_heads=2)
shape = cfgs.ShapeConfig("smoke", 64, 8, "train")
opt = get_optimizer("adamw")
fn = api.make_train_step(cfg, opt)
params_struct = api.param_shapes(cfg)
opt_struct = jax.eval_shape(opt.init, params_struct)
bs = api.batch_specs(cfg, shape)
with mesh:
    jitted = jax.jit(fn, in_shardings=(
        mesh_lib.sharding_tree(mesh, api.param_pspecs(cfg)),
        mesh_lib.sharding_tree(mesh, api.opt_state_pspecs(cfg, "adamw")),
        mesh_lib.sharding_tree(mesh, None),
        mesh_lib.sharding_tree(mesh, {k: v[1] for k, v in bs.items()})))
    lowered = jitted.lower(params_struct, opt_struct,
                           jax.ShapeDtypeStruct((), jnp.int32),
                           {k: v[0] for k, v in bs.items()})
    compiled = lowered.compile()
ca = compiled.cost_analysis()
from repro.analysis import hlo
coll = hlo.collective_summary(compiled.as_text())
print(json.dumps({"flops": ca.get("flops", 0),
                  "ar": coll["all-reduce"]["count"]}))
"""


def test_dryrun_smoke_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, cwd=".",
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["ar"] > 0        # data-parallel gradient sync exists
