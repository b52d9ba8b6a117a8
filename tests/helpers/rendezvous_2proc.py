"""2-process jax.distributed rendezvous helper (multi_process.py analog):
each rank initializes through init_parallel_env (coordinator = endpoint
0), asserts the global device view spans both processes, and all-reduces
its rank across them via a psum over the global mesh."""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.distributed import comm  # noqa: E402

env = comm.init_parallel_env()
rank = env.rank
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2, jax.device_count()

# cross-process collective: psum of (rank+1) over the job-wide dp mesh
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

g = comm._default_group()
val = np.full((1,), float(rank + 1), np.float32)

def prog(x):
    return jax.lax.psum(x, "dp")

f = comm.shard_map(prog, g.mesh, in_specs=P("dp"), out_specs=P())
arr = jax.make_array_from_process_local_data(
    NamedSharding(g.mesh, P("dp")), val, (2,)
)
out = f(arr)
total = float(np.asarray(jax.device_get(out))[0] if np.asarray(
    jax.device_get(out)).ndim else jax.device_get(out))
assert total == 3.0, total  # 1 + 2 across the two processes

with open(os.environ["RDV_LOG"] + f".rank{rank}", "w") as fh:
    fh.write(json.dumps({"rank": rank, "world": env.world_size,
                         "psum": total}))
print(f"rank {rank} rendezvous OK psum={total}")
sys.exit(0)
