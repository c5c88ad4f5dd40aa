"""Checkpoint/resume contract — orbax-backed, with integrity verification.

Reference parity: the platform delegates checkpointing to frameworks and
guarantees restart semantics + durable paths (SURVEY.md §5.4). Here orbax
async checkpointing is the in-tree contract; the controller guarantees the
same checkpoint dir across gang restarts, so `restore_latest` + step-offset
resume is all a trainer needs for fault tolerance.

Integrity layer (docs/health.md): orbax's atomic-rename commit protects
against *torn* saves (a partial write never becomes visible), but not
against a committed step whose bytes later rot or get scribbled on — and a
corrupt NEWEST step turns "restart from checkpoint" into a crash loop.
Every committed step therefore gets a content-checksum manifest
(kftpu-manifest.json inside the step dir); restore_latest verifies the
chosen step against it, quarantines a corrupt step out of the checkpoint
tree, and falls back to the previous verified step. Counters land in the
process-global kftpu_ckpt_verify_* registry (kubeflow_tpu/health.py) and a
fallback opens a `checkpoint.fallback` span in the worker's trace.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
import time
from typing import Any

import jax

from kubeflow_tpu.analysis.lockcheck import make_lock
from kubeflow_tpu.health import CKPT_MANIFEST_NAME, ckpt_verify_bump


def _ocp():
    """`orbax.checkpoint`, imported where a checkpoint is first made and not
    with the trainer: the import pulls in `google.cloud.logging`, whose
    packages check their dependencies' versions as they are imported
    (`importlib.metadata.packages_distributions()`, twice: every installed
    distribution's RECORD parsed, every listed file stat-ed). On the
    benchmark's machines the import was 26-35 s of every process that
    imported `kubeflow_tpu.train`, 24-32 s of it those checks, and it moved
    by 8 s with nothing but the heap's state (PERF.md Findings, PR 36)."""
    import orbax.checkpoint as ocp

    return ocp


class Checkpointer:
    """Thin orbax CheckpointManager wrapper with a stable save/restore API."""

    def __init__(self, directory: str, max_to_keep: int = 3,
                 async_save: bool = True, keep_best_metric: str | None = None,
                 best_mode: str = "max", verify: bool = True):
        """keep_best_metric: retain the max_to_keep BEST checkpoints by this
        eval-metric key (passed via save(metrics=...)) instead of the newest
        — the model-selection contract (restore_best serves the winner).
        verify: write per-step checksum manifests and verify-on-restore with
        quarantine + fallback (docs/health.md)."""
        self.directory = os.path.abspath(directory)
        self.keep_best_metric = keep_best_metric
        self.verify = verify
        os.makedirs(self.directory, exist_ok=True)
        self._mgr_kwargs = dict(
            max_to_keep=max_to_keep,
            enable_async_checkpointing=async_save,
        )
        if keep_best_metric:
            self._mgr_kwargs.update(
                best_fn=lambda m: float(m[keep_best_metric]),
                best_mode=best_mode,
            )
        self._async = async_save
        self._manifest_mu = make_lock("checkpoint.Checkpointer._manifest_mu")
        self._mgr = self._open()
        #: the steps the manager kept at the last save (None before the
        #: first): every other step on disk is being deleted by orbax's
        #: finalize thread, and a manifest written into it fails that
        #: thread's rmtree (ENOTEMPTY) and with it the next save
        self._kept: set[int] | None = None

    def _open(self):
        return _ocp().CheckpointManager(
            self.directory,
            options=_ocp().CheckpointManagerOptions(**self._mgr_kwargs),
        )

    def _reopen(self) -> None:
        """Rebuild the orbax manager after the on-disk step set changed
        underneath it (a quarantine): its cached step list must not keep
        serving — or GC'ing — a step that is no longer there."""
        self._mgr.close()
        self._mgr = self._open()

    def save(self, step: int, state: Any,
             metrics: dict | None = None) -> None:
        """metrics participate in best-ranking (keep_best_metric mode);
        metric-LESS saves are preserved outside the ranking (rescue/resume
        saves) and never become best_step."""
        if (metrics is not None and self.keep_best_metric
                and self.keep_best_metric not in metrics):
            raise ValueError(
                f"keep_best_metric {self.keep_best_metric!r} not in metrics "
                f"{sorted(metrics)} — fix TrainerConfig.keep_best_metric"
            )
        # under the manifest lock: orbax picks the steps to delete inside
        # save() and starts deleting them behind it, so no writer may be
        # half-way through one of them, and the next must know which went
        with self._manifest_mu:
            self._mgr.save(
                step, args=_ocp().args.StandardSave(state),
                **({"metrics": metrics} if metrics is not None else {}),
            )
            self._kept = set(self._mgr.all_steps())
        if self.verify:
            # sync mode: the step is committed, manifest inline. Async mode
            # hashes on a helper thread that first WAITS for this step's
            # commit to land — the whole point of async checkpointing is
            # that the training loop never blocks on checkpoint-sized I/O,
            # but the newest step is exactly the one a crash leaves behind,
            # so it must not stay unmanifested until the next save.
            if self._async:
                self._spawn_manifest_writer(step)
            else:
                with self._manifest_mu:
                    self._write_manifests()

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def best_step(self) -> int | None:
        return self._mgr.best_step()

    def restore_best(self, abstract_state: Any) -> tuple[int, Any] | None:
        """Restore the best-metric checkpoint (keep_best_metric mode).

        Verification applies but fallback does not: "second-best" is not a
        meaningful stand-in for a corrupt best — the step is quarantined and
        None returned so the caller decides."""
        if not self.keep_best_metric:
            # orbax best_step() falls back to latest_step() when best
            # tracking is off — silently serving the newest (possibly
            # worst) checkpoint as "best" must be an error instead
            raise ValueError(
                "restore_best requires a Checkpointer constructed with "
                "keep_best_metric (the mode is not persisted in the "
                "checkpoint directory)"
            )
        step = self._mgr.best_step()
        if step is None:
            return None
        if self.verify:
            verdict = self._verify_step(step)
            if verdict is False:
                self._quarantine(step)
                return None
            # same accounting contract as restore_latest: model-selection
            # restores must not vanish from the kftpu_ckpt_verify_* series
            ckpt_verify_bump(
                "steps_verified_total" if verdict
                else "unverified_restores_total")
        restored = self._mgr.restore(
            step, args=_ocp().args.StandardRestore(abstract_state)
        )
        return step, restored

    def restore_latest(self, abstract_state: Any) -> tuple[int, Any] | None:
        """Restore the newest VERIFIED checkpoint into the structure/
        shardings of `abstract_state` (a real or jax.eval_shape state).
        A newest step that fails its manifest is quarantined and the next-
        newest verified step served instead, so a corrupt save can cost at
        most one checkpoint interval, never the whole run. None if empty."""
        if not self.verify:
            step = self._mgr.latest_step()
            if step is None:
                return None
            return step, self._mgr.restore(
                step, args=_ocp().args.StandardRestore(abstract_state))

        quarantined: list[int] = []   # moved out of the tree
        unmovable: list[int] = []     # corrupt but the move itself failed
        steps = sorted(self._mgr.all_steps())
        while steps:
            step = steps.pop()
            verdict = self._verify_step(step)
            if verdict is False:
                # even when the quarantine move fails (ENOSPC, EACCES) the
                # corrupt step must still be SKIPPED — serving flipped
                # bytes is never an option — but telemetry must not claim
                # a removal that didn't happen
                (quarantined if self._quarantine(step)
                 else unmovable).append(step)
                continue
            if verdict is None:
                # no manifest (pre-verify checkpoint, or a crash between
                # commit and manifest): restorable, but say so in metrics
                ckpt_verify_bump("unverified_restores_total")
            else:
                ckpt_verify_bump("steps_verified_total")
            if quarantined or unmovable:
                from kubeflow_tpu.tracing import get_tracer

                ckpt_verify_bump("fallback_restores_total")
                attrs = {"step": step,
                         "quarantined": ",".join(map(str, quarantined))}
                if unmovable:
                    attrs["skipped_unmovable"] = ",".join(map(str, unmovable))
                with get_tracer().span("checkpoint.fallback", **attrs):
                    restored = self._mgr.restore(
                        step, args=_ocp().args.StandardRestore(abstract_state))
            else:
                restored = self._mgr.restore(
                    step, args=_ocp().args.StandardRestore(abstract_state))
            return step, restored
        return None

    # ----------------------------------------------------------- integrity

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, str(step))

    def _step_files(self, step: int) -> list[str]:
        """Relative paths of one committed step's payload files (manifest
        and writer tmp files excluded), sorted for a stable manifest."""
        root = self._step_dir(step)
        out: list[str] = []
        for dirpath, _dirnames, filenames in os.walk(root):
            for name in filenames:
                if name == CKPT_MANIFEST_NAME or name.endswith(".tmp"):
                    continue
                out.append(os.path.relpath(os.path.join(dirpath, name), root))
        return sorted(out)

    def _spawn_manifest_writer(self, step: int) -> None:
        """One short-lived daemon thread per async save: it waits (off the
        training thread, by watching the directory — never by touching the
        manager, which is not thread-safe) for THIS step's atomic commit to
        appear, then manifests every committed step still lacking one.
        Overlapping writers are idempotent: manifest existence is checked
        under the lock."""
        def run():
            deadline = time.time() + 120.0
            path = self._step_dir(step)
            while time.time() < deadline and not os.path.isdir(path):
                time.sleep(0.05)
            with self._manifest_mu:
                self._write_manifests()

        threading.Thread(target=run, name="ckpt-manifest", daemon=True).start()

    def _committed_steps(self) -> list[int]:
        """Committed steps straight from the directory: orbax's commit is
        an atomic rename to the bare step number (in-flight saves live in
        non-numeric tmp dirs), so a numeric dir IS a complete step. Disk
        enumeration keeps the manifest writer independent of the manager's
        cached step list (and of its thread-affinity)."""
        try:
            return sorted(
                int(n) for n in os.listdir(self.directory)
                if n.isdigit()
                and os.path.isdir(os.path.join(self.directory, n))
            )
        except OSError:
            return []

    def _write_manifests(self) -> None:
        """Checksum-manifest every committed step that lacks one and that
        the manager keeps."""
        for step in self._committed_steps():
            if self._kept is not None and step not in self._kept:
                continue
            root = self._step_dir(step)
            manifest = os.path.join(root, CKPT_MANIFEST_NAME)
            if os.path.exists(manifest):
                continue
            files = {}
            try:
                for rel in self._step_files(step):
                    files[rel] = {
                        "sha256": _sha256(os.path.join(root, rel)),
                        "size": os.path.getsize(os.path.join(root, rel)),
                    }
                tmp = manifest + ".tmp"
                with open(tmp, "w", encoding="utf-8") as fh:
                    json.dump({"step": step, "files": files,
                               "created": time.time()}, fh)
                os.replace(tmp, manifest)
            except OSError:
                continue  # a racing GC removed the step mid-walk
            ckpt_verify_bump("manifests_written_total")

    def _verify_step(self, step: int) -> bool | None:
        """True = checksums match, False = corrupt, None = no manifest."""
        root = self._step_dir(step)
        manifest = os.path.join(root, CKPT_MANIFEST_NAME)
        try:
            with open(manifest, "r", encoding="utf-8") as fh:
                want = json.load(fh)["files"]
        except (OSError, ValueError, KeyError):
            if not os.path.exists(manifest):
                return None
            ckpt_verify_bump("steps_corrupt_total")
            return False  # unreadable manifest IS corruption
        have = set(self._step_files(step))
        if set(want) - have:  # missing payload files
            ckpt_verify_bump("steps_corrupt_total")
            return False
        for rel, meta in want.items():
            path = os.path.join(root, rel)
            try:
                if (os.path.getsize(path) != meta["size"]
                        or _sha256(path) != meta["sha256"]):
                    ckpt_verify_bump("steps_corrupt_total")
                    return False
            except OSError:
                ckpt_verify_bump("steps_corrupt_total")
                return False
        return True

    def _quarantine(self, step: int) -> bool:
        """Move a corrupt step out of the checkpoint tree (never delete:
        the bytes are evidence) and re-open the manager so its cached step
        list forgets it. Holds the manifest lock: an in-flight async
        manifest writer is still using the manager being replaced. Returns
        False when the move itself failed (the step is still on disk —
        callers must skip it but not report it removed)."""
        with self._manifest_mu:
            dst = os.path.join(self.directory, "quarantine",
                               f"{step}-{int(time.time() * 1000)}")
            try:
                os.makedirs(os.path.dirname(dst), exist_ok=True)
                shutil.move(self._step_dir(step), dst)
            except OSError:
                return False
            ckpt_verify_bump("steps_quarantined_total")
            self._reopen()
        from kubeflow_tpu.tracing import get_tracer

        get_tracer().event("checkpoint.quarantine", step=step, moved_to=dst)
        return True

    def wait(self) -> None:
        self._mgr.wait_until_finished()
        if self.verify:
            with self._manifest_mu:  # joins any in-flight async writer
                self._write_manifests()

    def close(self) -> None:
        self._mgr.wait_until_finished()
        if self.verify:
            with self._manifest_mu:
                self._write_manifests()
        self._mgr.close()


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()
