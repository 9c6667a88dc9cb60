"""Pretrained-weight ingestion with hash verification.

Capability parity with ref: ResNet/tensorflow/models/resnet50v2.py:137-153
— the reference downloads keras-applications release weights by URL and
verifies a file hash before loading. Here ingestion is file-first (this
framework runs in egress-restricted TPU environments): verify the
sha256/md5 of a local artifact against the expected digest, then hand it
to the matching importer (torch .pt / keras .h5). Downloading, when the
environment allows it, is the caller's concern (e.g. ``gsutil cp`` in the
launch tooling).
"""

from __future__ import annotations

import hashlib
from pathlib import Path


def file_digest(path: str | Path, algorithm: str = "sha256") -> str:
    with open(path, "rb") as fh:
        return hashlib.file_digest(fh, algorithm).hexdigest()


def verify_artifact(
    path: str | Path, expected_digest: str, algorithm: str = "sha256"
) -> Path:
    """Return ``path`` if its digest matches; raise otherwise (the
    reference's file_hash check, resnet50v2.py:146-151)."""
    path = Path(path)
    got = file_digest(path, algorithm)
    if got != expected_digest.lower():
        raise ValueError(
            f"{path} {algorithm} mismatch: got {got}, "
            f"expected {expected_digest}"
        )
    return path


def load_pretrained(
    path: str | Path,
    *,
    expected_digest: str | None = None,
    algorithm: str = "sha256",
):
    """Verified pretrained checkpoint → Flax variables.

    Dispatches on suffix: ``.pt``/``.pth`` → convert.torch_import,
    ``.h5``/``.hdf5`` → convert.keras_import.
    """
    path = Path(path)
    if expected_digest is not None:
        verify_artifact(path, expected_digest, algorithm)
    suffix = path.suffix.lower()
    if suffix in (".pt", ".pth"):
        from deepvision_tpu.convert.torch_import import (
            load_torch_checkpoint,
            resnet_torch_to_flax,
        )

        return resnet_torch_to_flax(load_torch_checkpoint(path))
    if suffix in (".h5", ".hdf5"):
        from deepvision_tpu.convert.keras_import import keras_h5_to_flax

        return keras_h5_to_flax(path)
    raise ValueError(f"unrecognized checkpoint format: {path.name}")
