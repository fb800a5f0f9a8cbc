"""Device mesh and tier-submesh utilities.

The reference's notion of a "device" is a physical Jetson board reached over
SSH (src/models/server_manager.py).  Here a device tier is a **submesh of TPU
chips** carved out of the process's device list: the nano tier gets a 1-chip
mesh, the orin tier a ``tp``-chip mesh whose chips are ICI neighbors, and both
models are resident simultaneously on disjoint submeshes of one pod (the JAX
global-device default is deliberately avoided — every engine computation is
pinned to its tier's mesh).

When fewer chips exist than requested (a one-chip machine), tiers shrink
gracefully and may share chips — the framework still runs, with tiers
distinguished by model size alone.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np

from ..config import ClusterConfig, TierConfig


def tp_mesh(devices: Sequence[jax.Device], tp: int,
            axis_name: str = "tp") -> jax.sharding.Mesh:
    """A 1-D tensor-parallel mesh over the first ``tp`` devices."""
    chosen = np.array(list(devices[:tp]))
    return jax.sharding.Mesh(chosen, (axis_name,))


def replica_mesh(devices: Sequence[jax.Device], replicas: int,
                 tp: int = 1) -> jax.sharding.Mesh:
    """A 2-D ('batch', 'tp') tier mesh over the first replicas·tp
    devices — the data-parallel replica axis (each row is one engine
    replica's private submesh; serving/replicas.py slices it row by
    row).  'batch' deliberately matches the P('batch') data-parallel
    axis convention so per-replica batching reads as what it is."""
    chosen = np.array(list(devices[:replicas * tp])).reshape(replicas, tp)
    return jax.sharding.Mesh(chosen, ("batch", "tp"))


def sp_tp_mesh(devices: Sequence[jax.Device], sp: int,
               tp: int) -> jax.sharding.Mesh:
    """A 2-D ('sp', 'tp') tier mesh over the first sp·tp devices —
    sequence-parallel ring prefill × tensor-parallel weights."""
    chosen = np.array(list(devices[:sp * tp])).reshape(sp, tp)
    return jax.sharding.Mesh(chosen, ("sp", "tp"))


def ep_tp_mesh(devices: Sequence[jax.Device], ep: int,
               tp: int = 1) -> jax.sharding.Mesh:
    """('ep','tp') tier submesh: whole experts shard over 'ep' (the
    serving twin of the trainer's expert axis), attention heads and KV
    over 'tp'."""
    devices = list(devices)
    if len(devices) < ep * tp:
        raise ValueError(f"ep_tp_mesh: need {ep * tp} devices for "
                         f"ep={ep}×tp={tp}, have {len(devices)}")
    return jax.sharding.Mesh(
        np.asarray(devices[:ep * tp]).reshape(ep, tp), ("ep", "tp"))


def carve_tier_meshes(
    cluster: ClusterConfig,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Dict[str, jax.sharding.Mesh]:
    """Assign disjoint chip submeshes to tiers, in declaration order.

    Allocation: nano claims its ``tp`` chips first, orin the next ``tp``.
    Shortfall policy (in order):
      1. shrink a tier's tp to the largest divisor of its head counts that
         still fits the remaining chips;
      2. if nothing remains, share from the start of the device list.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)

    meshes: Dict[str, jax.sharding.Mesh] = {}
    cursor = 0
    for tier in cluster.tiers():
        if tier.endpoint:
            continue        # cross-host tier: its chips live on that host
        remaining = len(devices) - cursor
        tp = _fit_tp(tier, max(remaining, 0))
        if tp == 0:
            # Nothing left — share chips from the front (single-chip box).
            tp = max(_fit_tp(tier, len(devices)), 1)
            ep = _fit_ep(tier, len(devices), tp)
            sp = _fit_sp(tier, len(devices), tp)
            meshes[tier.name] = (
                ep_tp_mesh(devices, ep, tp) if ep > 1
                else sp_tp_mesh(devices, sp, tp) if sp > 1
                else tp_mesh(devices, tp))
            continue
        ep = _fit_ep(tier, remaining, tp)
        sp = _fit_sp(tier, remaining, tp) if ep == 1 else 1
        rep = (_fit_replicas(tier, remaining, tp)
               if ep == 1 and sp == 1 else 1)
        meshes[tier.name] = (
            ep_tp_mesh(devices[cursor:], ep, tp) if ep > 1
            else sp_tp_mesh(devices[cursor:], sp, tp) if sp > 1
            else replica_mesh(devices[cursor:], rep, tp) if rep > 1
            else tp_mesh(devices[cursor:], tp))
        cursor += tp * max(sp, ep, rep)
    return meshes


def _fit_replicas(tier: TierConfig, available: int, tp: int) -> int:
    """Device rows a replicated tier can claim (ISSUE 12): up to
    ``tier.replicas`` disjoint tp-sized slices, shrinking gracefully to
    what the box has left — replicas beyond the available slices share
    devices process-locally (serving/replicas.py _split_devices), so a
    short box degrades placement, never the replica count.  An
    autoscale-armed tier (ISSUE 18) claims slices for its MAX width:
    a replica the autoscaler adds later must land on its own devices,
    and the carve happens once at build time — devices reserved for
    elastic headroom sit idle at min width, which is exactly the
    capacity the autoscaler is trusted to spend."""
    want = tier.replicas
    if getattr(tier, "autoscale", False):
        want = max(want, int(getattr(tier, "autoscale_max_replicas",
                                     want)))
    if want <= 1:
        return 1
    return max(1, min(want, available // max(1, tp)))


def _fit_ep(tier: TierConfig, available: int, tp: int) -> int:
    """Largest expert-parallel degree ≤ requested that divides the
    model's expert count and fits the chips alongside tp.  1 for dense
    tiers (nothing to shard on 'ep')."""
    experts = tier.model().num_experts
    if tier.ep <= 1 or experts <= 1:
        return 1
    ep = min(tier.ep, max(available // tp, 1), experts)
    while ep > 1 and experts % ep:
        ep -= 1
    return max(ep, 1)


def _fit_sp(tier: TierConfig, available: int, tp: int) -> int:
    """Largest power-of-two sequence-parallel degree ≤ requested that fits
    the remaining chips alongside tp (power of two so it divides the
    power-of-two prefill buckets).  Returns 1 — reserving no extra chips —
    for tiers whose engine cannot use the sp axis (only the dense
    sequential InferenceEngine runs ring prefill)."""
    if tier.sp > 1 and (tier.model().num_experts > 1
                        or tier.decode_batch > 1 or tier.draft_preset):
        import logging
        logging.getLogger(__name__).warning(
            "tier %s: sp=%d ignored — sequence-parallel prefill needs the "
            "dense sequential engine (MoE=%s decode_batch=%d draft=%s); "
            "not reserving extra chips",
            tier.name, tier.sp, tier.model().num_experts > 1,
            tier.decode_batch, tier.draft_preset)
        return 1
    sp = 1
    while (sp * 2 <= tier.sp and sp * 2 * tp <= available):
        sp *= 2
    return sp


def requested_tp(tier: TierConfig) -> int:
    """The tier's requested tensor-parallel degree.  Feasibility clamps
    (head divisibility, available chips) run after this in ``_fit_tp``."""
    return max(1, tier.tp)


def _fit_tp(tier: TierConfig, available: int) -> int:
    """Largest feasible tensor-parallel degree ≤ requested, dividing the
    model's kv-head count (GQA shards whole kv heads)."""
    if available <= 0:
        return 0
    cfg = tier.model()
    tp = min(requested_tp(tier), available)
    while tp > 1 and (cfg.num_kv_heads % tp or cfg.num_heads % tp):
        tp -= 1
    return max(tp, 1)


def training_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    *,
    num_kv_heads: int,
    seq_len: int,
) -> jax.sharding.Mesh:
    """Factor the device list into a ('dp', 'sp', 'tp') training mesh using
    ALL devices for any count n.

    tp takes the largest divisor of n that also divides the kv-head count
    (whole GQA heads shard over tp); sp the largest divisor of the remainder
    that divides seq_len; dp absorbs the rest.  dp always divides n, so
    callers size the batch as a multiple of ``mesh.shape['dp']`` (see
    Trainer) — there is no silent device-dropping fallback.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)

    def largest_divisor(m: int, dividing: int) -> int:
        best = 1
        for f in range(1, m + 1):
            if m % f == 0 and dividing % f == 0:
                best = f
        return best

    tp = largest_divisor(n, num_kv_heads)
    rest = n // tp
    sp = largest_divisor(rest, seq_len)
    if sp == rest and rest > 2:
        sp = largest_divisor(rest // 2, seq_len) if rest % 2 == 0 else sp
    dp = rest // sp
    arr = np.array(devices).reshape(dp, sp, tp)
    return jax.sharding.Mesh(arr, ("dp", "sp", "tp"))


def moe_training_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    *,
    num_experts: int,
) -> jax.sharding.Mesh:
    """A ('dp', 'ep') mesh for MoE training: ep takes the largest divisor
    of the device count that also divides the expert count (whole experts
    per shard), dp absorbs the rest."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    ep = 1
    for f in range(1, n + 1):
        if n % f == 0 and num_experts % f == 0:
            ep = f
    dp = n // ep
    arr = np.array(devices).reshape(dp, ep)
    return jax.sharding.Mesh(arr, ("dp", "ep"))


def describe_meshes(meshes: Dict[str, jax.sharding.Mesh]) -> str:
    parts = []
    for name, mesh in meshes.items():
        ids = [d.id for d in mesh.devices.flat]
        parts.append(f"{name}: {len(ids)} device(s) {ids}")
    return "; ".join(parts)
