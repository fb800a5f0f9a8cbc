"""ICI/DCN collectives for cross-chip coordination.

The reference's control-plane "collectives" are SSH round-trips (pexpect
sessions polling /health, src/models/server_manager.py); its perf strategy
sees only what the local host measured.  Here the equivalents ride the
interconnect as XLA collectives (BASELINE.json: "perf strategy health/latency
signals are allgathered over ICI"):

- ``allgather_health``: every mesh participant contributes its local perf
  window summary; every participant receives all of them in one all-gather.
  On a multi-host pod each host folds the gathered remote summaries into its
  PerfStrategy (routing/strategies.py ``merge_remote``) so routing decisions
  reflect global tier health, not just local observations.
- ``psum_scalar``: convenience reduction for liveness counting / quorum.
"""

from __future__ import annotations

from functools import partial


import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

# Health record layout, one row per participant:
HEALTH_FIELDS = ("total_latency_ms", "total_tokens", "ok_count", "n_samples")


def allgather_health(mesh: Mesh, per_device_stats: np.ndarray) -> np.ndarray:
    """All-gather per-participant health rows over the mesh interconnect.

    per_device_stats: [n_devices, k] — row i is device i's local summary
    (on one host this is built locally; on a pod each host contributes its
    own row and reads everyone's).
    Returns [n_devices, k], identical on every participant.
    """
    axis = mesh.axis_names[0]
    n = mesh.shape[axis]
    stats = jnp.asarray(per_device_stats, jnp.float32)
    if stats.shape[0] != n:
        raise ValueError(f"expected {n} rows for mesh axis '{axis}', "
                         f"got {stats.shape[0]}")

    @partial(shard_map, mesh=mesh, in_specs=P(axis, None),
             out_specs=P(None, None), check_vma=False)
    def gather(local):                       # local: [1, k]
        return jax.lax.all_gather(local[0], axis)   # [n, k] replicated

    return np.asarray(gather(stats))


def psum_scalar(mesh: Mesh, values: np.ndarray) -> float:
    """Sum one scalar per device across the mesh (liveness/quorum count)."""
    axis = mesh.axis_names[0]
    vals = jnp.asarray(values, jnp.float32).reshape(-1)

    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(),
             check_vma=False)
    def reduce(local):
        return jax.lax.psum(local[0], axis)

    return float(reduce(vals))


def summarize_perf_window(samples) -> np.ndarray:
    """PerfStrategy sample window -> one health row (HEALTH_FIELDS)."""
    lat = sum(s[0] for s in samples)
    tok = sum(s[1] for s in samples)
    ok = sum(1 for s in samples if s[2])
    return np.array([lat, tok, ok, len(samples)], np.float32)
