"""Scenario: the MESH-COLLECTIVE federated-distillation round.

On a real pod, each client is a rank on the ``data`` mesh axis and the
server's masked-mean aggregation is ONE all-reduce (DESIGN.md §3) — no hub.
This example demonstrates that mode with 8 host devices standing in for 8
clients: every rank filters its own proxy logits with its private KMeans-DRE
centroids, then ``masked_mean_logits_psum`` fuses them in a single psum.

Must be launched as a script (device count is fixed at jax init):
    PYTHONPATH=src python examples/fed_shardmap.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                              # noqa: E402
import jax.numpy as jnp                 # noqa: E402
import numpy as np                      # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.aggregation import masked_mean_logits, masked_mean_logits_psum  # noqa: E402
from repro.core.kmeans import kmeans_fit, min_dist_to_centroids  # noqa: E402

C, T, K, DIM = 8, 64, 10, 16
mesh = jax.make_mesh((C,), ("clients",))
key = jax.random.PRNGKey(0)

# per-client private centroids (stacked), proxy logits, and a proxy batch
class_means = jax.random.normal(key, (C, DIM)) * 6.0
centroids = class_means[:, None, :]                       # (C, 1, DIM) 1-centroid DRE
proxy_x = jnp.concatenate([
    class_means[i] + jax.random.normal(jax.random.fold_in(key, i), (T // C, DIM))
    for i in range(C)])                                    # (T, DIM) mixed proxy
logits = jax.random.normal(jax.random.fold_in(key, 99), (C, T, K))
threshold = jnp.full((C,), 4.0)


def client_round(cents, thr, logits_local):
    """Runs ON EACH RANK: filter own logits, aggregate via one psum."""
    d = min_dist_to_centroids(proxy_x, cents[0])           # (T,)
    mask = d <= thr[0]
    teacher, valid = masked_mean_logits_psum(logits_local[0], mask[None][0],
                                             "clients")
    return teacher[None], valid[None], mask[None]


fn = jax.shard_map(client_round, mesh=mesh,
                   in_specs=(P("clients"), P("clients"), P("clients")),
                   out_specs=(P("clients"), P("clients"), P("clients")))
teacher_sharded, valid, masks = fn(centroids, threshold, logits)

# reference: hub-and-spoke masked mean with the same masks
ref_teacher, ref_valid = masked_mean_logits(logits, masks)

np.testing.assert_allclose(np.asarray(teacher_sharded)[0],
                           np.asarray(ref_teacher), rtol=1e-5, atol=1e-6)
print(f"devices: {jax.device_count()} (one per client)")
print(f"ID fraction per client: {np.asarray(masks).mean(axis=1).round(2)}")
print(f"psum teacher == hub teacher ✓  (valid samples: "
      f"{int(np.asarray(ref_valid).sum())}/{T})")
