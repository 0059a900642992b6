"""Direction-parallel ZO training across pods -- PocketLLM Sec 6.3 realized.

Runs in a subprocess-fresh interpreter with 8 placeholder devices forming
a (pod=2, data=2, model=2) mini production mesh, and demonstrates:

  1. K perturbation directions evaluated concurrently (vmap axis sharded
     over the pod axis),
  2. cross-pod traffic = the (K,) scalar vector gs (inspect the HLO:
     the only cross-pod collective is scalar-sized),
  3. straggler drop: masking one pod's direction yields a valid update,
  4. elastic: "losing a pod" = halving K; no parameter resharding.

  PYTHONPATH=src python examples/multipod_directions.py
"""

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core import MezoConfig, build_strategy
from repro.data.synthetic import lm_batch_at, synthetic_lm_corpus
from repro.models import build_model, sharding as shd
from repro.roofline.hlo import collective_bytes


def main():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    cfg = get_config("qwen3-4b").reduced(d_model=64, n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params = jax.device_put(params, shd.sharding_tree(params, mesh))

    stream = synthetic_lm_corpus(8 * 40 * 33, cfg.vocab, 0)
    batch = {k: jax.device_put(
        jnp.asarray(v), NamedSharding(mesh, P("data")))
        for k, v in lm_batch_at(0, 8, 32, cfg.vocab, stream).items()}

    mcfg = MezoConfig(eps=1e-2, lr=1e-2, n_directions=2)  # 1 per pod

    # the materialized estimator: its K-way vmap axis is what shards
    strat = build_strategy("vmapdir", "sgd")

    def step(cfg, mask=None):
        state, aux = strat.step(model.loss, strat.init_state(params, cfg),
                                batch, jnp.uint32(0), cfg, mask)
        return state.params, aux

    with jax.set_mesh(mesh):
        lowered = strat.lower(model.loss, strat.init_state(params, mcfg),
                              batch, jnp.uint32(0), mcfg, None)
        hlo = lowered.compile().as_text()
        coll = collective_bytes(hlo)
        _, aux = step(mcfg)
        # straggler: drop direction 1 (pod 1 late) -- still a valid step
        p3, _ = step(mcfg, jnp.array([1.0, 0.0]))
        # elastic: pod left -> K=1, same params sharding, no resharding
        p4, _ = step(MezoConfig(eps=1e-2, lr=1e-2, n_directions=1))

    print(f"gs per direction: {np.asarray(aux.gs)}")
    print(f"collective bytes/step/device: {coll.get('total', 0):,} "
          f"(params: {sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params)):,} bytes)")
    print("straggler-masked update == K=1 update:",
          np.allclose(np.asarray(p3['ln_f']['scale']),
                      np.asarray(p4['ln_f']['scale']), atol=1e-6))
    assert np.isfinite(np.asarray(aux.gs)).all()
    print("OK: direction-parallel, straggler drop and elastic-K all work")


if __name__ == "__main__":
    main()
