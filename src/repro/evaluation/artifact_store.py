"""Content-addressed artifact store: compiled executables that survive
the process.

The disk evaluation cache (:mod:`repro.evaluation.disk_cache`) persists
*scalar* estimator values; the compiled executables themselves stayed
memory-only, so a server booting after an exploration — a different
process — had to recompile the winning architecture even though the
study had already paid for it.  This store closes that gap: it persists
the serialized XLA executable (via
``jax.experimental.serialize_executable``) plus the artifact's static
analysis, content-addressed by the same identity the evaluation cache
uses, so ``python -m repro.launch.serve --from-report`` performs **zero**
XLA compiles for any program the exploration touched.

Content key
-----------
An entry's identity is the estimator program key — ``(name, mesh_scope,
batch, full architecture signature[, effective kernel schedules])`` —
wrapped with the **toolchain salt** (jax/jaxlib versions plus the
backend platform and ``device_kind``, the same salt
:func:`repro.evaluation.disk_cache.canonical_key` applies).  Every part
is load-bearing:

  * the *full* signature (layers AND pre-processing) — two candidates
    share an entry iff they are the same program (the cache-collision
    class of bug the property tests in ``tests/test_property.py`` pin);
  * ``mesh_scope`` not target name — the compiled program depends on the
    mesh topology only, so single-chip targets reuse each other's blobs;
  * the *effective* (shape-clamped) kernel-schedule signature — two
    requested schedules that clamp to the same launch share one entry,
    two that clamp apart never collide;
  * the toolchain salt — a jax/jaxlib upgrade, or a store written on
    another platform, structurally misses instead of deserializing an
    executable built by a different compiler for a different device.

Layout
------
``<dir>/artifacts/manifest.jsonl`` — append-only JSONL manifest under
the same ``flock`` + CRC32 discipline as the value cache: one record
``{"key": <canonical>, "blob": <sha256>, "meta": {...}, "crc": ...}``
per store; corrupt records read back as misses.  ``<dir>/artifacts/
<sha256>.bin`` — the pickled ``(payload, in_tree, out_tree)`` triple
from ``serialize_executable.serialize`` plus the analysis scalars.  The
blob name is the sha256 of the canonical key, so a re-store of the same
content is a no-op and two different keys can never share a blob.

Degradation
-----------
Executable serialization is platform/version dependent; every failure
path (serialize raises, unpickle fails, deserialize rejects the
payload, blob missing or torn) degrades to a miss — the caller
recompiles, exactly as before the store existed.  ``REPRO_ARTIFACTS=0``
disables the store wholesale (registered in :mod:`repro.envvars`).
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import warnings
from typing import Any, Dict, Hashable, Optional, Tuple

from repro import faults
from repro.envvars import read_env
from repro.evaluation.disk_cache import canonical_key
from repro.ioutils import locked_append

ARTIFACTS_ENV = "REPRO_ARTIFACTS"

_PICKLE_PROTOCOL = 4  # stable across the supported interpreters


def store_enabled() -> bool:
    """False when ``REPRO_ARTIFACTS=0`` disables executable persistence."""
    return read_env(ARTIFACTS_ENV, True)


def serialize_compiled(compiled: Any) -> Optional[bytes]:
    """Pickled ``(payload, in_tree, out_tree)`` for a compiled executable,
    or None when the platform/toolchain cannot serialize it."""
    try:
        from jax.experimental import serialize_executable as se

        payload, in_tree, out_tree = se.serialize(compiled)
        return pickle.dumps((payload, in_tree, out_tree), _PICKLE_PROTOCOL)
    except Exception:
        return None


def deserialize_compiled(blob: bytes) -> Optional[Any]:
    """Inverse of :func:`serialize_compiled`; None on any failure."""
    try:
        from jax.experimental import serialize_executable as se

        payload, in_tree, out_tree = pickle.loads(blob)
        return se.deserialize_and_load(payload, in_tree, out_tree)
    except Exception:
        return None


def content_hash(canonical: str) -> str:
    return hashlib.sha256(canonical.encode("utf-8")).hexdigest()


def _manifest_crc(key: str, blob: str) -> int:
    import zlib

    return zlib.crc32(json.dumps([key, blob], sort_keys=True,
                                 separators=(",", ":")).encode("utf-8"))


class ArtifactStore:
    """Content-addressed executable store next to a disk value cache.

    ``dir`` is the evaluation-cache store directory; blobs and the
    manifest live in an ``artifacts/`` subdirectory so the two tiers
    share one location (and one ``cache.dir`` spec knob).
    """

    SUBDIR = "artifacts"
    MANIFEST = "manifest.jsonl"

    def __init__(self, path: str):
        from repro.evaluation.disk_cache import CACHE_DIR_ENV

        override = read_env(CACHE_DIR_ENV, None)
        base = str(override) if override else str(path)
        self.path = os.path.join(base, self.SUBDIR)
        self._manifest = os.path.join(self.path, self.MANIFEST)
        self._lock = threading.Lock()
        self._index: Dict[str, Dict[str, Any]] = {}  # canonical key -> record
        self._offset = 0
        self.hits = 0
        self.misses = 0
        self.puts = 0
        self.bad_blobs = 0  # blobs that failed to load/deserialize
        os.makedirs(self.path, exist_ok=True)
        self.refresh()

    # -- manifest ----------------------------------------------------------

    def refresh(self) -> int:
        with self._lock:
            return self._read_new()

    def _read_new(self) -> int:
        if not os.path.exists(self._manifest):
            return 0
        try:
            with open(self._manifest, "rb") as f:
                f.seek(self._offset)
                data = f.read()
        except OSError:
            return 0
        lines = data.split(b"\n")
        self._offset += len(data) - len(lines[-1])
        n = 0
        for raw in lines[:-1]:
            if not raw.strip():
                continue
            try:
                rec = json.loads(raw.decode("utf-8"))
            except (UnicodeDecodeError, json.JSONDecodeError):
                continue
            if not isinstance(rec, dict):
                continue
            key, blob = rec.get("key"), rec.get("blob")
            if not isinstance(key, str) or not isinstance(blob, str):
                continue
            if rec.get("crc") != _manifest_crc(key, blob):
                continue  # torn/rotted record: a miss, never a wrong program
            self._index[key] = rec
            n += 1
        return n

    # -- keys --------------------------------------------------------------

    @staticmethod
    def canonical(key: Hashable) -> Optional[str]:
        """The store's canonical string key: the evaluation-cache program
        key wrapped with the toolchain salt.  None = not storable (the
        key contains non-JSON parts, e.g. an uncacheable candidate)."""
        if isinstance(key, tuple) and any(k is None for k in key):
            return None
        return canonical_key(key)

    def keys(self):
        with self._lock:
            return list(self._index)

    def __contains__(self, key: Hashable) -> bool:
        ck = self.canonical(key)
        if ck is None:
            return False
        with self._lock:
            self._read_new()
            return ck in self._index

    def __len__(self) -> int:
        with self._lock:
            return len(self._index)

    # -- store/load --------------------------------------------------------

    def put(self, key: Hashable, artifact: Any) -> bool:
        """Persist one compiled artifact; returns True when (newly or
        already) stored.  Never raises: an unserializable executable or
        an unwritable store degrades to memory-only, same as the value
        cache."""
        if not store_enabled():
            return False
        ck = self.canonical(key)
        if ck is None:
            return False
        with self._lock:
            self._read_new()
            if ck in self._index:
                return True  # content-addressed: same key == same program
        payload = serialize_compiled(artifact.compiled)
        if payload is None:
            return False
        meta = {
            "flops": float(artifact.flops),
            "bytes_accessed": float(artifact.bytes_accessed),
            "collective_bytes": float(artifact.collective_bytes),
            "memory": {k: int(v) for k, v in artifact.memory.items()},
            "schedules": artifact.schedules,
        }
        blob_name = content_hash(ck)
        blob_path = os.path.join(self.path, blob_name + ".bin")
        try:
            if not os.path.exists(blob_path):
                tmp = blob_path + f".tmp.{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.write(payload)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, blob_path)  # atomic: readers never see a torn blob
            line = json.dumps({"key": ck, "blob": blob_name, "meta": meta,
                               "crc": _manifest_crc(ck, blob_name)}) + "\n"
            locked_append(self._manifest, line)
        except (OSError, faults.InjectedFault) as e:
            warnings.warn(
                f"artifact store append to {self._manifest!r} failed ({e!r}); "
                f"the executable stays memory-only", RuntimeWarning,
                stacklevel=2)
            return False
        with self._lock:
            self._index[ck] = {"key": ck, "blob": blob_name, "meta": meta}
            self.puts += 1
            self._read_new()  # consume our own append (offset hygiene)
        return True

    def get(self, key: Hashable, target: Any = None) -> Optional[Any]:
        """Load one compiled artifact, rebound to ``target``; None on miss
        or any deserialization failure (the caller recompiles)."""
        if not store_enabled():
            return None
        ck = self.canonical(key)
        if ck is None:
            return None
        with self._lock:
            if ck not in self._index:
                self._read_new()  # a sibling may have stored it since
            rec = self._index.get(ck)
            if rec is None:
                self.misses += 1
                return None
        blob_path = os.path.join(self.path, str(rec["blob"]) + ".bin")
        try:
            with open(blob_path, "rb") as f:
                payload = f.read()
        except OSError:
            with self._lock:
                self.bad_blobs += 1
                self.misses += 1
            return None
        compiled = deserialize_compiled(payload)
        if compiled is None:
            with self._lock:
                self.bad_blobs += 1
                self.misses += 1
            return None
        artifact = self._rebuild(rec.get("meta") or {}, compiled, target)
        with self._lock:
            self.hits += 1
        return artifact

    def _rebuild(self, meta: Dict[str, Any], compiled: Any, target: Any):
        from repro.hwgen.generator import Artifact
        from repro.hwgen.roofline import roofline_terms
        from repro.hwgen.targets import get_target

        if isinstance(target, str):
            target = get_target(target)
        flops = float(meta.get("flops", 0.0))
        bytes_accessed = float(meta.get("bytes_accessed", 0.0))
        coll = float(meta.get("collective_bytes", 0.0))
        roofline = None
        if target is not None:
            roofline = roofline_terms(
                hlo_flops=flops, hlo_bytes=bytes_accessed,
                collective_bytes=coll, n_chips=1, chip=target.chip)
        return Artifact(
            target=target,
            compiled=compiled,
            flops=flops,
            bytes_accessed=bytes_accessed,
            collective_bytes=coll,
            memory={k: int(v) for k, v in (meta.get("memory") or {}).items()},
            roofline=roofline,
            example_args=(),
            schedules=meta.get("schedules"),
        )

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._index),
                "hits": self.hits,
                "misses": self.misses,
                "puts": self.puts,
                "bad_blobs": self.bad_blobs,
            }
