"""Subprocess: validate hierarchical/compressed collectives on an 8-device
virtual mesh (2 pods × 2 data × 2 model). Prints OK lines; the parent test
asserts on them."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import _make_mesh
from repro.core.collectives import (hierarchical_psum_local,
                                    compressed_cross_pod_psum_local,
                                    hierarchical_psum)

mesh = _make_mesh((2, 2, 2), ("pod", "data", "model"))

x = jnp.arange(24.0).reshape(2, 12) / 7.0

# 1. hierarchical == flat psum over (data, pod)
flat = jax.shard_map(lambda v: jax.lax.psum(v, ("data", "pod")), mesh=mesh,
                     in_specs=P(None, None), out_specs=P(None, None),
                     check_vma=False)(x)
hier = jax.shard_map(partial(hierarchical_psum_local, in_axis="data",
                             cross_axis="pod"),
                     mesh=mesh, in_specs=P(None, None),
                     out_specs=P(None, None), check_vma=False)(x)
np.testing.assert_allclose(np.asarray(hier), np.asarray(flat), rtol=1e-6)
print("OK hierarchical==flat")

# 2. wrapper path
hier2 = hierarchical_psum(x, mesh)
np.testing.assert_allclose(np.asarray(hier2), np.asarray(flat), rtol=1e-6)
print("OK wrapper")

# 3. compressed psum ≈ flat psum, error bounded by int8 quantization
err0 = jnp.zeros((x.size // 2,), jnp.float32)
comp, new_err = jax.shard_map(
    partial(compressed_cross_pod_psum_local, in_axis="data", cross_axis="pod"),
    mesh=mesh, in_specs=(P(None, None), P(None)),
    out_specs=(P(None, None), P(None)), check_vma=False)(x, err0)
rel = float(jnp.max(jnp.abs(comp - flat)) / jnp.max(jnp.abs(flat)))
assert rel < 0.02, rel
print("OK compressed rel_err=%.4f" % rel)

# 4. error feedback: residual is nonzero and bounded by one quant step
assert float(jnp.max(jnp.abs(new_err))) <= float(jnp.max(jnp.abs(x))) * 2 / 127 + 1e-6
print("OK error-feedback")

# 5. hierarchical psum on single-pod mesh (no 'pod' axis)
mesh2 = _make_mesh((4, 2), ("data", "model"))
flat2 = jax.shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh2,
                      in_specs=P(None, None), out_specs=P(None, None),
                      check_vma=False)(x)
hier3 = hierarchical_psum(x, mesh2)
np.testing.assert_allclose(np.asarray(hier3), np.asarray(flat2), rtol=1e-6)
print("OK single-pod fallback")


# 6. distributed HPL: sharded blocked LU == single-device factors
from repro.core.hpl import blocked_lu, make_test_matrix, distributed_hpl_setup
a, _ = make_test_matrix(256)
lu_ref = blocked_lu(a, nb=64)
fn, _, sharding = distributed_hpl_setup(mesh2, 256, nb=64)
with mesh2:
    lu_dist = fn(jax.device_put(a, sharding))
np.testing.assert_allclose(np.asarray(lu_dist), np.asarray(lu_ref),
                           rtol=2e-4, atol=2e-4)
print("OK distributed-hpl")
print("ALL_OK")
