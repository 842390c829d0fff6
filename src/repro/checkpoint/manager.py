"""Uncoordinated pod-local checkpointing (the paper's FT substrate).

Each pod owns a complete FSDP replica of the training state (see
parallel/sharding.py), so a pod checkpoints *independently* of other pods:
its own timer cadence with a pod-specific phase offset (uncoordinated —
avoids synchronized I/O bursts, paper §2.2), async background writes, and
checkpoint *move-ahead* (paper §4.1): a pod about to idle can snapshot
early so its next timer checkpoint is absorbed into otherwise-wasted time.

Storage layout (atomic via tmp+rename):
    root/pod_<i>/step_<n>/arrays.npz     flat {path: array}
    root/pod_<i>/step_<n>/meta.json      step, wall time, leaf manifest
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil
import threading
import time
from typing import Any, Optional

import jax
import numpy as np

__all__ = ["CheckpointConfig", "PodCheckpointManager"]


def _flatten(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(
            str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
            for k in path)
        out[key] = np.asarray(leaf)
    return out


def _unflatten_into(example, flat: dict):
    leaves_paths, treedef = jax.tree_util.tree_flatten_with_path(example)
    leaves = []
    for path, leaf in leaves_paths:
        key = "/".join(
            str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
            for k in path)
        arr = flat[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"checkpoint shape mismatch at {key}: "
                             f"{arr.shape} vs {leaf.shape}")
        if arr.dtype.kind == "V":
            # .npz keeps dtypes numpy does not know (bfloat16) as raw bytes
            arr = arr.view(leaf.dtype)
        leaves.append(arr.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    root: str
    interval_steps: int = 100
    keep: int = 2
    async_save: bool = True
    # uncoordinated phase offsets: pod i first checkpoints at
    # interval * (1 + jitter_frac * frac(hash(i)))
    jitter_frac: float = 0.5
    # explicit phase: every pod first checkpoints at step
    # interval_steps - phase_offset_steps (jitter_frac is then ignored).
    # The adaptive-controller reconciliation path uses 1, which puts the
    # first save exactly interval_steps * step_time of execution after the
    # renewal engine's age-0 start (docs/runtime.md).
    phase_offset_steps: Optional[int] = None


class PodCheckpointManager:
    """One per pod.  Timer (step-count) cadence with a pod-specific offset."""

    def __init__(self, cfg: CheckpointConfig, pod_id: int):
        self.cfg = cfg
        self.pod_id = pod_id
        self.dir = pathlib.Path(cfg.root) / f"pod_{pod_id}"
        self.dir.mkdir(parents=True, exist_ok=True)
        # deterministic pod phase (Python's hash() is per-process salted)
        import zlib
        self._phase = (zlib.crc32(f"pod-{pod_id}".encode()) % 1000) / 1000.0
        self._offset = self._phase_offset()
        self._pending: Optional[threading.Thread] = None
        self.saves = 0
        self.move_aheads = 0

    def _phase_offset(self) -> int:
        if self.cfg.phase_offset_steps is not None:
            return int(self.cfg.phase_offset_steps)
        return int(self.cfg.interval_steps * self.cfg.jitter_frac * self._phase)

    def set_interval_steps(self, interval_steps: int) -> None:
        """Re-cadence a live manager (the adaptive controller's policy
        push).  Takes effect at the next ``due`` check: the anchor stays the
        latest saved step, so the next checkpoint fires ``interval_steps``
        after it under the new interval."""
        if interval_steps < 1:
            raise ValueError(f"interval_steps must be >= 1, got {interval_steps}")
        self.cfg = dataclasses.replace(self.cfg, interval_steps=int(interval_steps))
        self._offset = self._phase_offset()

    # --- cadence -----------------------------------------------------------

    def due(self, step: int) -> bool:
        last = self.latest_step()
        anchor = last if last is not None else -self._offset
        return step - anchor >= self.cfg.interval_steps

    def age_steps(self, step: int) -> int:
        last = self.latest_step()
        return step + self._offset if last is None else step - last

    # --- save/restore ------------------------------------------------------

    def save(self, step: int, state, *, move_ahead: bool = False) -> None:
        """Snapshot the state.  ``move_ahead`` marks a paper-§4.1 early
        checkpoint taken while entering a wait phase."""
        self.wait()
        host_state = jax.tree.map(np.asarray, jax.device_get(state))

        def _write():
            tmp = self.dir / f".tmp_step_{step}"
            final = self.dir / f"step_{step}"
            tmp.mkdir(parents=True, exist_ok=True)
            flat = _flatten(host_state)
            np.savez(tmp / "arrays.npz", **flat)
            (tmp / "meta.json").write_text(json.dumps({
                "step": step,
                "pod": self.pod_id,
                "time": time.time(),
                "move_ahead": move_ahead,
                "leaves": sorted(flat.keys()),
            }))
            if final.exists():
                shutil.rmtree(final)
            tmp.rename(final)
            self._gc()

        self.saves += 1
        if move_ahead:
            self.move_aheads += 1
        if self.cfg.async_save:
            self._pending = threading.Thread(target=_write, daemon=True)
            self._pending.start()
        else:
            _write()

    def maybe_save(self, step: int, state) -> bool:
        if self.due(step):
            self.save(step, state)
            return True
        return False

    def wait(self) -> None:
        if self._pending is not None:
            self._pending.join()
            self._pending = None

    def latest_step(self) -> Optional[int]:
        self.wait()
        steps = sorted(int(p.name.split("_")[1]) for p in self.dir.glob("step_*"))
        return steps[-1] if steps else None

    def restore(self, example_state, step: Optional[int] = None):
        """Restore into the structure of ``example_state`` (shapes checked)."""
        self.wait()
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint for pod {self.pod_id}")
        with np.load(self.dir / f"step_{step}" / "arrays.npz") as z:
            flat = {k: z[k] for k in z.files}
        return step, _unflatten_into(example_state, flat)

    def _gc(self) -> None:
        steps = sorted(int(p.name.split("_")[1]) for p in self.dir.glob("step_*"))
        for s in steps[: -self.cfg.keep]:
            shutil.rmtree(self.dir / f"step_{s}", ignore_errors=True)
