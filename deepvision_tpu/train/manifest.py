"""Checkpoint integrity manifests: the pure-file half of PR 4's story.

A committed epoch's sidecar ``manifest-<epoch>.json`` records per-file
size + SHA-256 for everything under the step directory. This module
holds the write/verify primitives WITHOUT importing Orbax (or jax), so
two kinds of consumers can share one implementation:

- ``train/checkpoint.CheckpointManager`` (the writer, post-commit);
- the cluster supervisor (``resilience/cluster.py``), a jax-free parent
  process that must pick "the newest commonly-verified epoch" before
  relaunching a preempted multi-host job — it verifies and quarantines
  with nothing but file hashes.

Concurrency contract: ``write_manifest`` stages through a tmp file
UNIQUE to the writer (pid + monotonic counter) and commits with one
atomic ``os.replace``. Two hosts of a multi-process run racing the same
epoch's commit (a preemption barrier interrupted mid-save) therefore
leave either the old or the new COMPLETE manifest — never interleaved
or truncated bytes — and a writer killed mid-stage leaves only its own
tmp file, which verification ignores.
"""

from __future__ import annotations

import itertools
import json
import os
import shutil
from pathlib import Path

MANIFEST_VERSION = 1

_tmp_seq = itertools.count()


def _hash_file(path: Path) -> str:
    """Streaming SHA-256 — the repo's ONE implementation."""
    from deepvision_tpu.convert.pretrained import file_digest

    return file_digest(path, "sha256")


def manifest_path(root: str | Path, epoch: int) -> Path:
    return Path(root) / f"manifest-{epoch}.json"


def step_dir(root: str | Path, epoch: int) -> Path:
    return Path(root) / str(epoch)


def write_manifest(root: str | Path, epoch: int,
                   extra: dict | None = None) -> None:
    """Hash the committed epoch directory into its sidecar. Atomic and
    multi-writer-safe: the tmp name is unique per (pid, call), so
    concurrent writers each stage complete bytes and the last
    ``os.replace`` wins with a valid file.

    ``extra`` merges additional audited fields into the sidecar —
    notably ``state_fingerprint`` (resilience/sentinel.py), the
    save-time random-projection fingerprint of the in-memory state:
    SHA-256 proves the bytes on disk match the bytes that were
    written; the fingerprint lets a verified restore prove those bytes
    match the state the trainer MEANT to save (corruption that
    predates serialization)."""
    root = Path(root)
    sdir = step_dir(root, epoch)
    if not sdir.exists():  # e.g. keep_best evicted it already
        return
    files = {
        str(p.relative_to(sdir)): {
            "size": p.stat().st_size,
            "sha256": _hash_file(p),
        }
        for p in sorted(sdir.rglob("*")) if p.is_file()
    }
    manifest = {"version": MANIFEST_VERSION, "epoch": int(epoch),
                "files": files, **(extra or {})}
    target = manifest_path(root, epoch)
    tmp = target.with_suffix(
        f".json.tmp.{os.getpid()}.{next(_tmp_seq)}")
    tmp.write_text(json.dumps(manifest))
    os.replace(tmp, target)


def read_manifest(root: str | Path, epoch: int) -> dict | None:
    """The committed sidecar as a dict (None when absent/unreadable) —
    how the verified restore reads the audited ``state_fingerprint``."""
    try:
        return json.loads(manifest_path(root, epoch).read_text())
    except (OSError, ValueError):
        return None


def verify_manifest(root: str | Path, epoch: int) -> tuple[bool, str]:
    """-> (ok, reason). An epoch with NO manifest verifies vacuously
    (pre-integrity checkpoints stay restorable); an unreadable or
    mismatching manifest fails it."""
    root = Path(root)
    sdir = step_dir(root, epoch)
    if not sdir.exists():
        return False, "step directory missing"
    mp = manifest_path(root, epoch)
    if not mp.exists():
        return True, "no manifest (pre-integrity checkpoint)"
    try:
        manifest = json.loads(mp.read_text())
        files = manifest["files"]
        for rel, want in files.items():
            p = sdir / rel
            if not p.is_file():
                return False, f"missing file {rel}"
            if p.stat().st_size != want["size"]:
                return False, (f"size mismatch {rel}: "
                               f"{p.stat().st_size} != {want['size']}")
            if _hash_file(p) != want["sha256"]:
                return False, f"checksum mismatch {rel}"
    except (ValueError, KeyError, TypeError, AttributeError,
            OSError) as e:
        # parses-but-wrong-schema manifests and files vanishing
        # mid-scan are corruption too — verification must FAIL
        # them, never crash on them
        return False, f"unreadable/malformed manifest: {e}"
    return True, "ok"


def fs_epochs(root: str | Path) -> list[int]:
    """Epoch dirs actually on disk, ascending."""
    root = Path(root)
    if not root.exists():
        return []
    return sorted(int(p.name) for p in root.iterdir()
                  if p.is_dir() and p.name.isdigit())


def newest_verified_epoch(root: str | Path, *, quarantine: bool = False,
                          log=print) -> int | None:
    """Newest-first scan returning the first epoch whose manifest
    verifies. With ``quarantine``, failing epochs are MOVED to
    ``quarantine/`` on the way past (evidence, not deletion) — the
    single-writer form of ``CheckpointManager.restore_verified``'s
    fallback that the cluster supervisor runs before relaunching a
    degraded job (no Orbax, no jax, no collective restore needed)."""
    root = Path(root)
    for epoch in reversed(fs_epochs(root)):
        ok, why = verify_manifest(root, epoch)
        if ok:
            return epoch
        log(f"[ckpt-integrity] epoch {epoch}: {why}"
            + ("; quarantining" if quarantine else ""), flush=True)
        if quarantine:
            qroot = root / "quarantine"
            qroot.mkdir(exist_ok=True)
            target = qroot / str(epoch)
            n = 0
            while target.exists():
                n += 1
                target = qroot / f"{epoch}.{n}"
            shutil.move(str(step_dir(root, epoch)), str(target))
            mp = manifest_path(root, epoch)
            if mp.exists():
                shutil.move(str(mp), str(target) + ".manifest.json")
    return None
