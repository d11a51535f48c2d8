"""Checkpointing substrate (paper §4).

* **Dual checkpointing** — two full-checkpoint slots (ckpt-1 / ckpt-2),
  alternating by age; a failure mid-write never destroys the only valid
  checkpoint. Writes are atomic (tmp dir + rename) and a MANIFEST with step
  + leaf checksums marks validity.
* **Persistent model-only checkpointing** — parameters only (8x smaller
  than a full AdamW checkpoint in bf16 mixed precision), kept at every
  interval (never rotated) so training can be tracked back to a good regime
  after divergence; restoring one reinitializes optimizer states.
* **DP-scattered model checkpointing** — model-parallel shard m is written
  by DP rank (m % DP), spreading filesystem load across nodes instead of
  concentrating all writes on dp_index 0 (``dp_scattered_writers``).
* **Model broadcasting** — in multi-host deployments only one rank loads
  from the filesystem and broadcasts (paper uses torch.broadcast/all_reduce);
  single-process JAX gets this for free via ``jax.device_put`` replication,
  recorded here as ``broadcast_params`` for API parity.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation


# ---------------------------------------------------------------------------
# pytree <-> flat npz
# ---------------------------------------------------------------------------

def _flatten(tree):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path)
        out[key] = np.asarray(leaf)
    return out


def save_pytree(tree, path: str):
    np.savez(path, **_flatten(tree))


def load_pytree(template, path: str):
    data = np.load(path)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    new = []
    for p, leaf in leaves:
        key = jax.tree_util.keystr(p)
        arr = data[key]
        assert arr.shape == tuple(leaf.shape), f"{key}: {arr.shape}"
        new.append(arr.astype(leaf.dtype) if hasattr(leaf, "dtype") else arr)
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template), new)


def _checksum(d: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(d):
        h.update(k.encode())
        h.update(np.ascontiguousarray(d[k]).tobytes()[:4096])
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# DP-scattered write assignment
# ---------------------------------------------------------------------------

def dp_scattered_writers(num_model_shards: int, dp_size: int) -> dict:
    """shard m -> writing DP rank (paper: d = m % DP)."""
    return {m: m % dp_size for m in range(num_model_shards)}


def broadcast_params(params, mesh=None):
    """Load-once-broadcast (paper §4 'Model Broadcasting'). In single-process
    JAX, placing the host array on a replicated sharding performs exactly one
    host->devices broadcast rather than per-rank filesystem loads."""
    if mesh is None:
        return params
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P())), params)


# ---------------------------------------------------------------------------
# Checkpointer
# ---------------------------------------------------------------------------

class Checkpointer:
    """Dual + model-only checkpointing; mesh-sharded states are supported by
    gather-on-save (``np.asarray`` on a single-process sharded jax.Array
    assembles the global array) and reshard-on-restore (restored host arrays
    are ``device_put`` back onto ``shardings``, so an SO/EPSO run resumes
    with the exact placement it was jitted for). This covers the pipeline
    stage axis too: a pp-stage-sharded layer stack is gathered into one
    stage-agnostic (L, ...) array on disk and resharded back onto its
    P('pp', ...) placement on restore, so checkpoints are portable across
    pipeline layouts.

    With a ``plan`` (a resolved ParallelPlan), its spec + axis layout are
    serialized into each MANIFEST; ``restore`` then *refuses* to silently
    reshard a checkpoint written under a different axis layout — it raises
    a descriptive error unless the caller opts in with
    ``on_plan_mismatch='reshard'`` (an explicit re-plan: the host arrays are
    device_put onto the live plan's shardings).

    Live expert placement (parallel/placement.py): under EP rebalancing the
    expert stacks are saved in their *placed* order, and the live
    ``placement`` (kept current by the launcher) rides in the MANIFEST —
    ``restore`` surfaces it as ``restored_placement`` so the caller rebuilds
    the step against the exact placement the arrays were written under
    (resume bit-identical mid-rebalance-schedule). Placement does not change
    shardings, so ``layout_signature`` plan checks are orthogonal."""

    def __init__(self, root: str, *, interval: int = 1000,
                 model_only_interval: int = 0, shardings=None,
                 plan=None, on_plan_mismatch: str = "error",
                 placement=None):
        if on_plan_mismatch not in ("error", "reshard"):
            raise ValueError("on_plan_mismatch must be 'error' or 'reshard',"
                             f" got {on_plan_mismatch!r}")
        self.root = root
        self.interval = interval
        self.model_only_interval = model_only_interval or interval
        self.shardings = shardings       # state-shaped pytree or None
        self.plan = plan                 # ResolvedPlan or None
        self.on_plan_mismatch = on_plan_mismatch
        self.placement = placement       # live ExpertPlacement or None
        self.restored_placement = None   # set by restore()
        os.makedirs(root, exist_ok=True)
        self.slots = [os.path.join(root, "ckpt-1"),
                      os.path.join(root, "ckpt-2")]

    # ---- dual full checkpoints -------------------------------------------
    def _slot_manifest(self, slot: str):
        man = os.path.join(slot, "MANIFEST.json")
        if not os.path.exists(man):
            return None
        try:
            with open(man) as f:
                return json.load(f)
        except Exception:
            return None

    def _slot_step(self, slot: str) -> int:
        m = self._slot_manifest(slot)
        if m is None:
            return -1
        try:
            return int(m["step"]) if m.get("valid") else -1
        except Exception:
            return -1

    def _oldest_slot(self) -> str:
        steps = [self._slot_step(s) for s in self.slots]
        return self.slots[int(np.argmin(steps))]

    def save(self, state, step: int, *, fail_after_write: bool = False):
        """Write a full checkpoint into the *older* of the two slots.
        ``fail_after_write`` simulates a mid-checkpoint failure (tests)."""
        slot = self._oldest_slot()
        tmp = slot + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        flat = _flatten(state)
        np.savez(os.path.join(tmp, "state.npz"), **flat)
        if fail_after_write:      # crash before the manifest => slot invalid
            if os.path.exists(slot):
                shutil.rmtree(slot)
            os.rename(tmp, slot)
            return slot
        man = {"step": step, "valid": True, "time": time.time(),
               "checksum": _checksum(flat)}
        if self.plan is not None:
            man["plan"] = {"spec": self.plan.spec(),
                           "layout": self.plan.layout_signature()}
        if self.placement is not None:
            man["placement"] = self.placement.to_manifest()
        with open(os.path.join(tmp, "MANIFEST.json"), "w") as f:
            json.dump(man, f)
        if os.path.exists(slot):
            shutil.rmtree(slot)
        os.rename(tmp, slot)
        return slot

    def restore(self, template, *, shardings=None):
        """Restore from the newest *valid* slot, resharding each leaf onto
        ``shardings`` (falling back to the instance default) when given.
        Returns (state, step) or (None, -1).

        When both the manifest and this Checkpointer carry a plan, their
        axis layouts must agree — a mismatch raises instead of silently
        resharding onto whatever the caller passed (set
        ``on_plan_mismatch='reshard'`` to re-plan explicitly)."""
        self.restored_placement = None
        best, best_step = None, -1
        for slot in self.slots:
            s = self._slot_step(slot)
            if s > best_step:
                best, best_step = slot, s
        if best is None:
            return None, -1
        manifest = self._slot_manifest(best)
        self._check_plan(manifest, best)
        if (manifest or {}).get("placement") is not None:
            from repro.parallel.placement import ExpertPlacement
            self.restored_placement = ExpertPlacement.from_manifest(
                manifest["placement"])
        else:
            self.restored_placement = None
        state = load_pytree(template, os.path.join(best, "state.npz"))
        sh = shardings if shardings is not None else self.shardings
        if sh is not None:
            state = jax.tree.map(jax.device_put, state, sh)
        return state, best_step

    def _check_plan(self, manifest, slot: str) -> None:
        saved = (manifest or {}).get("plan")
        if saved is None or self.plan is None:
            return                       # legacy checkpoint or legacy caller
        live = {"spec": self.plan.spec(),
                "layout": self.plan.layout_signature()}
        if saved["layout"] == live["layout"]:
            return
        if self.on_plan_mismatch == "reshard":
            print(f"checkpoint {slot}: re-planning "
                  f"'{saved.get('spec')}' -> '{live['spec']}' "
                  f"(explicit on_plan_mismatch='reshard')")
            return
        raise ValueError(
            f"checkpoint {slot} was written under plan "
            f"'{saved.get('spec')}' (layout {saved['layout']}) but this run "
            f"is planned as '{live['spec']}' (layout {live['layout']}); "
            f"refusing to silently reshard — restart with the saved plan, "
            f"or pass on_plan_mismatch='reshard' to re-plan explicitly")

    # ---- persistent model-only checkpoints --------------------------------
    def save_model_only(self, params, step: int):
        path = os.path.join(self.root, f"model-{step:08d}.npz")
        save_pytree(params, path)
        return path

    def list_model_only(self):
        return sorted(f for f in os.listdir(self.root)
                      if f.startswith("model-") and f.endswith(".npz"))

    def restore_model_only(self, template, step: int):
        """Params from the model-only checkpoint at ``step``; the caller
        reinitializes optimizer states (paper: 'training can be restarted
        from just the model parameters')."""
        path = os.path.join(self.root, f"model-{step:08d}.npz")
        return load_pytree(template, path)

    # ---- hooks --------------------------------------------------------------
    def maybe_save(self, state, params, step: int):
        wrote = []
        if step > 0 and step % self.interval == 0:
            with TraceAnnotation("ckpt.save"):
                wrote.append(self.save(state, step))
        if step > 0 and step % self.model_only_interval == 0:
            with TraceAnnotation("ckpt.save"):
                wrote.append(self.save_model_only(params, step))
        return wrote
