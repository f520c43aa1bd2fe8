"""AOT executable store: the serve bucket menu as a restorable artifact.

The bucket-shape menu (:mod:`.bucketing`) makes steady-state serving
recompile-free, but every fresh process still pays one backend compile per
(query-bucket × candidate-bucket) combination before it can take traffic —
12.4 s of warmup in a CPU container (builders' round 7; on the chip not
measured as a metric: no cold-start cell yet, PERF.md §7), which the PR 6
``ReplicaRouter`` fleet pays on every replica restart and the PR 8
compile-stall health signal reads as a degraded window. This module
removes that cost: after :meth:`~.engine.QueryEngine.warmup`, the engine
serializes every compiled executable (``jax.experimental
.serialize_executable`` — the loaded XLA executable itself, not its HLO)
into a versioned sidecar next to the :class:`~.index.LinkageIndex`
artifact, and a fresh process restores the entire menu without ever
invoking the backend compiler (proven by the ``jax.monitoring`` compile
counter staying flat; gated by ``make warmup-smoke``).

A serialized executable is literal machine code bound to one exact
environment, so restore validity is STRICT — the sidecar meta records

  * the environment fingerprint (jax + jaxlib versions, backend, target
    features — for CPU the host ISA flag set, for accelerators the device
    kind/platform version — and the x64 switch), and
  * the engine binding (index content fingerprint + settings hash, dtype,
    top-k / brown-out budget, the full bucket menu, the fused-path flag),

and ANY mismatch invalidates the whole store with one structured
``serve_aot`` degradation event: the engine falls back to fresh compiles,
never a wrong or SIGILL-prone executable. Individual blobs are
sha256-bound by the meta (the atomic commit point, reusing the checkpoint
machinery), so a torn or tampered blob degrades that one shape to a fresh
compile instead of unpickling attacker-controlled bytes — a blob's pickle
payload is only ever deserialized AFTER its digest verifies against the
committed meta.

Durability mirrors the index artifact: blob files land first under
fingerprint-derived names, the meta JSON commits the set atomically, and
superseded blobs are swept only after the commit.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle

from ..resilience.checkpoint import atomic_write_bytes, atomic_write_json
from ..utils.logging_utils import warn_degraded

logger = logging.getLogger("splink_tpu")

AOT_FORMAT_VERSION = 1
MENU_NAME = "aot_menu.json"
BLOB_PREFIX = "exec-"


class AotStoreError(RuntimeError):
    """Unreadable / unwritable AOT sidecar."""


def _blob_file(name: str, digest: str) -> str:
    return f"{BLOB_PREFIX}{name}-{digest[:16]}.bin"


def serialize_executable(compiled) -> bytes:
    """One compiled executable (``jax.stages.Compiled``) to restorable
    bytes: the serialized XLA executable plus its argument pytree defs."""
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = se.serialize(compiled)
    return pickle.dumps((payload, in_tree, out_tree))


def deserialize_executable(blob: bytes):
    """Restore a :func:`serialize_executable` blob to a callable
    ``Compiled``. Trusts its input — callers verify the sha256 binding
    first (this is a pickle load)."""
    from jax.experimental import serialize_executable as se

    import jax

    payload, in_tree, out_tree = pickle.loads(blob)
    # the serve programs are single-device; without execution_devices the
    # loader binds the executable to EVERY local device and a multi-chip
    # host then refuses the one-shard arguments
    return se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=jax.local_devices()[:1],
    )


class AotStore:
    """One AOT sidecar directory (read side; :meth:`write` produces it)."""

    def __init__(self, directory: str | os.PathLike):
        self.directory = os.fspath(directory)
        self._entries: dict[str, dict] | None = None

    # -- read -----------------------------------------------------------

    def validate(self, binding: dict) -> bool:
        """Load the menu and check the full invalidation matrix against
        ``binding`` (the engine identity) and the CURRENT environment
        fingerprint. False (with exactly one structured degradation event
        naming every mismatched key) means the store must not be used and
        the caller compiles fresh."""
        from ..utils.envfp import environment_fingerprint

        menu_path = os.path.join(self.directory, MENU_NAME)
        try:
            with open(menu_path, encoding="utf-8") as fh:
                menu = json.load(fh)
        except FileNotFoundError:
            return False  # no sidecar: a plain cold start, not degraded
        except (OSError, json.JSONDecodeError, ValueError) as e:
            warn_degraded(
                "serve_aot",
                "unreadable",
                f"AOT sidecar meta at {menu_path} is unreadable ({e}); "
                "falling back to fresh compiles",
            )
            return False
        mismatches = []
        if menu.get("version") != AOT_FORMAT_VERSION:
            mismatches.append(
                f"format version {menu.get('version')!r} != "
                f"{AOT_FORMAT_VERSION}"
            )
        env = environment_fingerprint()
        saved_env = menu.get("environment") or {}
        for key, want in env.items():
            got = saved_env.get(key)
            if got != want:
                mismatches.append(
                    f"environment.{key} {got!r} != current {want!r}"
                )
        saved_binding = menu.get("binding") or {}
        for key, want in binding.items():
            got = saved_binding.get(key)
            if got != want:
                mismatches.append(f"binding.{key} {got!r} != {want!r}")
        if mismatches:
            warn_degraded(
                "serve_aot",
                "stale",
                "AOT sidecar invalidated (fresh compiles instead): "
                + "; ".join(mismatches),
                sidecar=self.directory,
            )
            return False
        self._entries = dict(menu.get("executables") or {})
        return True

    @property
    def names(self) -> list[str]:
        return sorted(self._entries or {})

    def restore(self, name: str):
        """Deserialize one executable by menu name, or None when the menu
        has no such entry or its blob is missing/corrupt (each corrupt
        blob emits one degradation event; the caller compiles fresh)."""
        if not self._entries:
            return None
        entry = self._entries.get(name)
        if entry is None:
            return None
        path = os.path.join(self.directory, entry["file"])
        try:
            with open(path, "rb") as fh:
                blob = fh.read()
        except OSError as e:
            warn_degraded(
                "serve_aot",
                "corrupt_blob",
                f"AOT executable {name!r} unreadable at {path} ({e}); "
                "compiling fresh",
            )
            return None
        digest = hashlib.sha256(blob).hexdigest()
        if digest != entry.get("sha256"):
            warn_degraded(
                "serve_aot",
                "corrupt_blob",
                f"AOT executable {name!r} at {path} does not match its "
                "committed fingerprint (torn write or tampering); "
                "compiling fresh",
            )
            return None
        try:
            return deserialize_executable(blob)
        except Exception as e:  # noqa: BLE001 - every restore failure degrades
            warn_degraded(
                "serve_aot",
                "restore_failed",
                f"AOT executable {name!r} failed to deserialize "
                f"({type(e).__name__}: {e}); compiling fresh",
            )
            return None

    # -- write ----------------------------------------------------------

    @classmethod
    def write(
        cls, directory: str | os.PathLike, binding: dict, executables: dict
    ) -> str:
        """Persist ``executables`` (menu name -> compiled executable) as a
        sidecar at ``directory``: blobs first under fingerprint-derived
        names, then the meta JSON as the atomic commit point, then a
        best-effort sweep of superseded blobs. Returns the meta path."""
        from ..utils.envfp import environment_fingerprint

        directory = os.fspath(directory)
        os.makedirs(directory, exist_ok=True)
        entries: dict[str, dict] = {}
        keep: set[str] = set()
        for name in sorted(executables):
            blob = serialize_executable(executables[name])
            digest = hashlib.sha256(blob).hexdigest()
            fname = _blob_file(name, digest)
            atomic_write_bytes(os.path.join(directory, fname), blob)
            entries[name] = {
                "file": fname,
                "sha256": digest,
                "bytes": len(blob),
            }
            keep.add(fname)
        menu = {
            "version": AOT_FORMAT_VERSION,
            "environment": environment_fingerprint(),
            "binding": binding,
            "executables": entries,
        }
        path = atomic_write_json(os.path.join(directory, MENU_NAME), menu)
        try:  # post-commit sweep (a leftover costs disk, never correctness)
            for fname in os.listdir(directory):
                if (
                    fname.startswith(BLOB_PREFIX)
                    and fname.endswith(".bin")
                    and fname not in keep
                ):
                    os.unlink(os.path.join(directory, fname))
        except OSError:  # pragma: no cover - sweep is best-effort
            pass
        logger.info(
            "AOT sidecar committed: %s (%d executables, %d bytes)",
            directory, len(entries),
            sum(e["bytes"] for e in entries.values()),
        )
        return path
