"""Quickstart — the paper's contribution in five minutes.

1. Fit GenModel to benchmark curves (here: the paper's own Table-5 fits).
2. Price the classic AllReduce plans and see the δ/ε trade-off.
3. Let GenTree pick the plan for a topology.
4. Execute exactly that plan as a JAX collective schedule and verify it
   against lax.psum.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P

from repro.core import cost_model as cm
from repro.core.collectives import allreduce
from repro.core.gentree import gentree
from repro.core.topology import single_switch

# -- 1. GenModel: T = A·α + B·β + C·γ + D·δ + max(w−w_t,0)·B·ε -------------
params = cm.GenModelParams()        # the paper's CPU-testbed fit
S = 1e8                             # 100M floats, like the paper

print("plan pricing at N=12 (seconds):")
for name, cost in [
        ("ring", cm.cost_ring(12, S, params)),
        ("cps (fan-in 12 > w_t=9 → incast!)", cm.cost_cps(12, S, params)),
        ("hcps 6×2 (the paper's sweet spot)",
         cm.cost_hcps([6, 2], S, params))]:
    print(f"  {name:40s} {cost:.3f}")

# -- 2. the two new optimalities cannot both hold (Theorem 2) ---------------
from repro.core import optimality, plans
p_cps = plans.cps(12, S)
p_ring = plans.ring(12, S)
print(f"\nCPS:  δ-optimal={optimality.is_delta_optimal(p_cps)} "
      f"ε-optimal={optimality.is_epsilon_optimal(p_cps, params.w_t)}")
print(f"Ring: δ-optimal={optimality.is_delta_optimal(p_ring)} "
      f"ε-optimal={optimality.is_epsilon_optimal(p_ring, params.w_t)}")

# -- 3. GenTree picks the plan for the topology -----------------------------
result = gentree(single_switch(12), S)
dec = result.decisions["root"]
print(f"\nGenTree on 12-server switch picks: {dec.algo} {dec.factors} "
      f"(predicted {result.predicted_time:.3f}s)")

# -- 4. run that plan as a JAX collective schedule --------------------------
mesh = make_mesh((8,), ("x",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 1000))

f = shard_map(
    lambda v: allreduce(v[0], "x", "hcps", factors=(4, 2))[None],
    mesh=mesh, in_specs=P("x"), out_specs=P("x"))
got = np.asarray(f(x))
want = np.asarray(x.sum(0))
assert np.allclose(got, np.tile(want, (8, 1)), rtol=1e-4, atol=1e-4)
print("\nhcps(4,2) AllReduce on an 8-device mesh matches lax.psum ✓")
