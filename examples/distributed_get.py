"""Range-partitioned distributed GET on a local mesh — the cluster-level
Bourbon read path (all-gather probes -> learned local lookup -> masked psum).

  PYTHONPATH=src python examples/distributed_get.py
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core.datasets import make_dataset
from repro.core.distributed import (DistStoreConfig, build_dist_get,
                                    build_dist_state)

keys = make_dataset("ar", 1 << 16, seed=2)
vptrs = np.arange(keys.shape[0], dtype=np.int64)
cfg = DistStoreConfig(n_keys=keys.shape[0], probe_batch=1 << 12)

mesh = jax.make_mesh((jax.device_count(),), ("data",),
                     axis_types=(AxisType.Explicit,))
# each shard's row on its own device
state = jax.device_put(build_dist_state(keys, vptrs, mesh.size, cfg),
                       NamedSharding(mesh, P("data")))
fn = build_dist_get(mesh, cfg)

rng = np.random.default_rng(0)
probes = jnp.asarray(rng.choice(keys, cfg.probe_batch))
with jax.set_mesh(mesh):
    found, vp = fn(state, probes)
print(f"devices={mesh.size} probes={cfg.probe_batch} "
      f"hit_rate={float(jnp.mean(found)):.3f}")
assert bool(jnp.all(found))
print("all probes answered by their owning range shard")
