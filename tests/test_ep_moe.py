"""Expert-parallel (shard_map) MoE == auto-sharded MoE, on 8 fake devices.

Runs in a subprocess because the device count must be fixed before jax
initializes (the main test process keeps the default 1 device).
"""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.models import moe as MoE
from repro.models.config import ModelConfig

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = ModelConfig(family="moe", n_layers=1, d_model=32, n_heads=4,
                  n_kv_heads=4, d_ff=64, vocab=64, n_experts=8, topk=2,
                  expert_dff=48, capacity_factor=8.0, dtype="float32")
key = jax.random.PRNGKey(0)
p = MoE.moe_init(cfg, key)
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 8, cfg.d_model))

want, aux_w = MoE.moe_apply(cfg, p, x)        # single-device reference

with jax.set_mesh(mesh):
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    ps = {"router": jax.device_put(p["router"], NamedSharding(mesh, P())),
          "w_in": jax.device_put(p["w_in"],
                                 NamedSharding(mesh, P("model", None, None))),
          "w_out": jax.device_put(p["w_out"],
                                  NamedSharding(mesh, P("model", None, None)))}
    got, aux_g = jax.jit(lambda pp, xx: MoE.moe_apply_ep(cfg, pp, xx))(ps, xs)

np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(float(aux_g), float(aux_w), rtol=1e-4)

# collective check: EP path must not all-reduce expert buffers
from repro.roofline.hlo import analyze
with jax.set_mesh(mesh):
    lowered = jax.jit(lambda pp, xx: MoE.moe_apply_ep(cfg, pp, xx)[0]).lower(
        jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                    sharding=l.sharding), ps),
        jax.ShapeDtypeStruct(xs.shape, xs.dtype, sharding=xs.sharding))
    a_ep = analyze(lowered.compile().as_text())
    lowered2 = jax.jit(lambda pp, xx: MoE.moe_apply(cfg, pp, xx)[0]).lower(
        jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                    sharding=l.sharding), ps),
        jax.ShapeDtypeStruct(xs.shape, xs.dtype, sharding=xs.sharding))
    a_auto = analyze(lowered2.compile().as_text())
print("EP coll:", a_ep["collective_bytes"], "AUTO coll:",
      a_auto["collective_bytes"])
assert a_ep["collective_bytes"] <= a_auto["collective_bytes"]
print("EP_MOE_OK")
"""


@pytest.mark.slow
def test_ep_moe_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], cwd=os.path.join(
        os.path.dirname(__file__), ".."), env=env, capture_output=True,
        text=True, timeout=600)
    assert "EP_MOE_OK" in r.stdout, r.stdout + r.stderr
