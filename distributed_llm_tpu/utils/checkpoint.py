"""Checkpoint/resume for model weights and trainer state (Orbax).

The reference has no model checkpointing — its models live inside Ollama
and its only persistent state is the routing cache's JSON round-trip
(SURVEY.md §5.4; kept as QueryRouter.save_cache/load_cache).  Owning the
models makes weight checkpointing a real subsystem:

- **Preemption-safe layout**: each ``Trainer.save`` writes a fresh
  ``<dir>/v<step>`` checkpoint (Orbax's own write is atomic), then swaps
  the ``<dir>/latest`` symlink and prunes all but the newest two versions.
  A kill at any instant leaves a valid, complete checkpoint behind —
  never a half-deleted one (force-overwriting in place would first remove
  the only good copy).
- **One copy of the weights**: the train state (params + optimizer
  moments + step) is written once; serving loads just the ``params``
  subtree via Orbax partial restore instead of keeping a second full
  copy of the weights on disk.
- **Restore is placement-aware**: targets carry explicit shardings, so a
  checkpoint from an 8-chip dp×tp mesh restores straight onto a 1-chip
  serving tier or a different training mesh — resharding happens at
  restore time, never as a conversion step.  (Without explicit shardings
  Orbax replays the *saved* topology, which does not exist on the new
  host.)
"""

from __future__ import annotations

import logging
import os
import re
from typing import Any, Dict, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from ..config import ModelConfig
from ..models import init_params as family_init_params

_VERSION_RE = re.compile(r"^v(\d+)$")


def _abspath(path: str) -> str:
    return os.path.abspath(os.path.expanduser(path))


def save_checkpoint(path: str, tree: Any) -> str:
    """Write a pytree of (possibly sharded) jax arrays. Overwrites."""
    path = _abspath(path)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(path, tree, force=True)
    return path


def restore_checkpoint(path: str, like: Any) -> Any:
    """Restore onto the structure/dtypes/shardings of ``like`` (a concrete
    or abstract-with-sharding pytree)."""
    with ocp.StandardCheckpointer() as ckptr:
        return ckptr.restore(_abspath(path), like)


def restore_subtree(path: str, like: Dict[str, Any]) -> Dict[str, Any]:
    """Partial restore: only the keys present in ``like`` are read; their
    leaves must be ShapeDtypeStructs WITH shardings (explicit placement)."""
    restore_args = ocp.checkpoint_utils.construct_restore_args(like)
    with ocp.PyTreeCheckpointer() as ckptr:
        return ckptr.restore(
            _abspath(path),
            args=ocp.args.PyTreeRestore(item=like, restore_args=restore_args,
                                        partial_restore=True))


def abstract_params(cfg: ModelConfig, shardings: Any) -> Any:
    """ShapeDtypeStruct tree for the model's params, annotated with the
    target shardings (a matching tree or a single Sharding for all)."""
    abstract = jax.eval_shape(lambda: family_init_params(cfg, seed=0))
    if not isinstance(shardings, (dict,)):
        shardings = jax.tree.map(lambda _: shardings, abstract)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)


# -- versioned train-state directories --------------------------------------

def _latest_dir(root: str) -> Optional[str]:
    link = os.path.join(_abspath(root), "latest")
    return os.path.realpath(link) if os.path.islink(link) else None


def _swap_latest(root: str, version_dir: str) -> None:
    """Atomically point <root>/latest at version_dir (symlink rename)."""
    link = os.path.join(root, "latest")
    tmp = os.path.join(root, ".latest.tmp")
    if os.path.lexists(tmp):
        os.unlink(tmp)
    os.symlink(os.path.basename(version_dir), tmp)
    os.replace(tmp, link)


def _prune_versions(root: str, keep: int = 2) -> None:
    import shutil
    current = _latest_dir(root)
    versions = sorted(
        (int(m.group(1)), os.path.join(root, d))
        for d in os.listdir(root)
        if (m := _VERSION_RE.match(d)) and os.path.isdir(os.path.join(root, d)))
    for _, d in versions[:-keep]:
        if os.path.realpath(d) != current:
            shutil.rmtree(d, ignore_errors=True)


def save_train_state(path: str, trainer) -> Optional[str]:
    """Checkpoint params + optimizer moments + step counter under a new
    ``v<step>`` version, then atomically publish it as ``latest``.

    Returns the checkpoint root, or **None when the save was skipped**
    because this exact step is already the published ``latest`` — the
    caller can then advance a step and retry if its state genuinely
    differs (resume from an older version reached by a different path);
    a log warning alone gave no programmatic signal."""
    root = _abspath(path)
    os.makedirs(root, exist_ok=True)
    version_dir = os.path.join(root, f"v{trainer.step_count}")
    if os.path.realpath(version_dir) == _latest_dir(root):
        # Already published at this exact step (save_every divided
        # max_steps, so the loop's save and the final save coincide).
        # The orbax save would force-overwrite the LIVE artifact in
        # place — a preemption mid-rewrite would leave 'latest' pointing
        # at a half-written dir, breaking the kill-at-any-instant
        # invariant.  In the in-run double-save case the state is
        # identical; a run that reaches the published step by a
        # DIFFERENT path (resumed from an older version) is discarded
        # here — None tells the caller, who can step once more to
        # publish such a state under a fresh version.
        logging.getLogger(__name__).warning(
            "save skipped: %s is already the published 'latest' at step "
            "%d; if this run's state differs (resume from an older "
            "version), advance one step so it publishes under a new "
            "version", version_dir, trainer.step_count)
        return None
    # A stale same-step dir from an abandoned/rolled-back run is NOT the
    # published artifact; orbax force-overwrites it below.
    save_checkpoint(os.path.join(version_dir, "state"), {
        "params": trainer.params,
        "opt_state": trainer.opt_state,
        "step": np.asarray(trainer.step_count, np.int64),
    })
    _swap_latest(root, version_dir)
    _prune_versions(root)
    return root


def _mesh_like(tree: Any, mesh: jax.sharding.Mesh) -> Any:
    """Abstract restore target pinned to the mesh: leaves keep their
    NamedSharding if they have one, everything else (e.g. optax's scalar
    step counters, created uncommitted at eager init) restores replicated.
    Restoring onto a committed single-device placement instead would make
    the next jitted step fail its cross-device consistency check."""
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def leaf(x):
        sharding = getattr(x, "sharding", None)
        if not isinstance(sharding, jax.sharding.NamedSharding):
            sharding = replicated
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype
                                    if hasattr(x, "dtype")
                                    else np.asarray(x).dtype,
                                    sharding=sharding)

    return jax.tree.map(leaf, tree)


def load_train_state(path: str, trainer) -> None:
    """Resume from <path>/latest in place, onto the trainer's mesh."""
    latest = _latest_dir(path)
    if latest is None:
        raise FileNotFoundError(f"no 'latest' checkpoint under {path!r}")
    restored = restore_checkpoint(os.path.join(latest, "state"), {
        "params": _mesh_like(trainer.params, trainer.mesh),
        "opt_state": _mesh_like(trainer.opt_state, trainer.mesh),
        "step": np.asarray(trainer.step_count, np.int64),
    })
    trainer.params = restored["params"]
    trainer.opt_state = restored["opt_state"]
    trainer.step_count = int(restored["step"])


def peek_vocab_size(path: str) -> Optional[int]:
    """Row count of the saved embedding table, read from checkpoint
    METADATA only (no tensor bytes) — lets scripts detect a
    stale-vocabulary artifact (e.g. a byte-level 512 vocab from before the
    subword migration) before trying to serve it.  None if unreadable."""
    latest = _latest_dir(path)
    target = os.path.join(latest, "state") if latest else _abspath(path)
    try:
        with ocp.PyTreeCheckpointer() as ckptr:
            meta = ckptr.metadata(target)
        # Orbax returns a StepMetadata whose pytree lives under
        # item_metadata.tree (older releases exposed .tree directly).
        tree = getattr(getattr(meta, "item_metadata", None), "tree", None)
        if tree is None:
            tree = getattr(meta, "tree", meta)
        embed = tree["params"]["embed"]
        return int(embed.shape[0])
    except Exception:
        return None


def load_params_for_tier(path: str, cfg: ModelConfig,
                         mesh: Optional[jax.sharding.Mesh] = None,
                         devices: Optional[Any] = None) -> Dict[str, Any]:
    """Load serving weights, placed for the tier's submesh (tensor-sharded
    when a mesh is given, single-device otherwise).  ``path`` may be a
    Trainer.save directory (its ``latest`` version's params subtree is
    read) or a weights-only checkpoint."""
    if mesh is not None:
        from ..parallel.sharding import param_shardings
        shardings: Any = param_shardings(cfg, mesh)
    else:
        dev = (list(devices)[0] if devices else jax.devices()[0])
        shardings = jax.sharding.SingleDeviceSharding(dev)
    like = abstract_params(cfg, shardings)

    latest = _latest_dir(path)
    if latest is not None:
        return restore_subtree(os.path.join(latest, "state"),
                               {"params": like})["params"]
    return restore_checkpoint(path, like)
