"""Multi-device driver: M-to-N message queue resharding across meshes."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.messages import MessageQueue, reshard

devs = jax.devices()
mesh_a = Mesh(np.array(devs[:4]).reshape(2, 2), ("data", "model"))
mesh_b = Mesh(np.array(devs[4:]).reshape(4, 1), ("data", "model"))

q = MessageQueue()
x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))

# 1-to-1 with resharding TP2 -> TP1, DP2 -> DP4
q.push("vit", "llm", "h0", xa)
got = q.pull("vit", "llm", "h0",
             sharding=NamedSharding(mesh_b, P("data", None)))
np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
assert got.sharding.mesh.shape["data"] == 4

# M-to-N: two senders push fragments of one tensor
q.push("teacher", "student", "h1", x[:4], frag_index=(slice(0, 4),),
       frag_rank=0, frag_count=2, global_shape=(8, 4))
q.push("teacher", "student", "h1", x[4:], frag_index=(slice(4, 8),),
       frag_rank=1, frag_count=2, global_shape=(8, 4))
got2 = q.pull("teacher", "student", "h1",
              sharding=NamedSharding(mesh_b, P("data", None)))
np.testing.assert_array_equal(np.asarray(got2), np.asarray(x))

# FIFO across keys, stats
q.push("a", "b", "k1", jnp.ones(3))
q.push("a", "b", "k2", jnp.zeros(3))
np.testing.assert_array_equal(np.asarray(q.pull("a", "b", "k2")),
                              np.zeros(3))
np.testing.assert_array_equal(np.asarray(q.pull("a", "b", "k1")),
                              np.ones(3))
assert q.stats()["pushes"] == 5

# direct reshard helper: TP4 <- TP2 style move
y = reshard(xa, mesh_b, P(None, "data"))
np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

# timeout on missing fragment
try:
    q.pull("a", "b", "missing", timeout=0.2)
    raise SystemExit("expected TimeoutError")
except TimeoutError:
    pass

print("DRIVER_OK messages")
