import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import fedml_tpu
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from fedml_tpu.core.multihost import MultiHostSpec, init_multihost

pid = int(sys.argv[1]); port = sys.argv[2]
spec = MultiHostSpec(coordinator=f"127.0.0.1:{port}", num_processes=2,
                     process_id=pid)
mesh = init_multihost(spec, client=2)
assert jax.device_count() == 2, jax.device_count()
x = jax.make_array_from_callback(
    (2,), NamedSharding(mesh, P("client")),
    lambda idx: jnp.full((1,), float(pid + 1)))
out = float(jax.jit(jnp.sum)(x))
print(f"proc {pid}: global sum = {out}", flush=True)
assert out == 3.0, out
