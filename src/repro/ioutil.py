"""Shared durable-I/O primitives: SHA-256 digests + atomic publishes,
and the placement of JAX's persistent compilation cache.

Two subsystems persist binary artifacts with integrity manifests — the
training checkpointer (:mod:`repro.checkpoint.checkpointer`) and the
compile cache (:mod:`repro.integrity.store`).  Both follow the same
crash-safety discipline, factored here so it is written (and tested)
once:

- **Hash the bytes on disk**, not the in-memory object: the digest
  covers exactly what a later reader will see, including serialization
  headers, so any truncation or bit rot fails the compare.
- **Write to a temporary name, then rename**: ``os.rename``/
  ``os.replace`` within a directory is atomic on POSIX, so a reader
  never observes a half-written file — after a crash the final name
  either holds the complete old content or the complete new content.
"""

from __future__ import annotations

import hashlib
import os
import shutil


def sha256_bytes(data: bytes) -> str:
    """Hex SHA-256 of ``data``."""
    return hashlib.sha256(data).hexdigest()


def sha256_file(path: str) -> str:
    """Hex SHA-256 of the file's current on-disk bytes."""
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def atomic_write_bytes(path: str, data: bytes) -> None:
    """Publish ``data`` at ``path`` atomically (tmp write + replace).

    The temporary lives in the target's directory so the final
    ``os.replace`` never crosses a filesystem boundary; ``fsync``
    before the rename orders the data ahead of the publish."""
    d = os.path.dirname(path) or "."
    tmp = os.path.join(d, f".tmp_{os.getpid()}_{os.path.basename(path)}")
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def atomic_replace_dir(tmp: str, final: str) -> None:
    """Atomically publish a fully-written staging directory at
    ``final`` (removing any previous version first) — the
    checkpointer's publish step."""
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)


#: JAX's own variable naming its persistent compilation cache directory.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    (a script's ``main``; never on import) and return its directory.

    Where :data:`COMPILE_CACHE_ENV` is set, JAX already reads it and no
    other directory is set.  Otherwise the cache goes to the fixed
    ``<root>/.jax_cache``, so a later run from the same checkout finds
    it.  A Pallas kernel compiles in a second or two, under JAX's
    default one-second floor for keeping an entry, so the floor is
    lowered to zero."""
    import jax
    directory = os.environ.get(COMPILE_CACHE_ENV)
    if not directory:
        directory = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory
