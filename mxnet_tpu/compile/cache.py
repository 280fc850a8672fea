"""On-disk program-artifact index (the ``CachedOp``-amortization story made
persistent).

The reference's ``CachedOp`` amortizes graph construction once per
*process*; XLA's whole-program model makes the compiled **executable** the
expensive artifact (arXiv:2301.13062), so warm starts require persisting it
across processes — the TVM ahead-of-time stance (arXiv:1802.04799).

:class:`ProgramCache` is a directory of serialized compiled programs keyed
by ``StableHLO fingerprint x backend x jax/jaxlib/mxnet_tpu versions``:

* ``index.json`` — the record list (key, file, bytes, sha256, version
  metadata, LRU timestamps), rewritten atomically (tmp + ``os.replace``)
  so a kill mid-write can never destroy it;
* ``<key>.bin`` — one blob per program, also written atomically.

Robustness contract (tested in ``tests/test_compile_cache.py``):

* a corrupt/truncated blob or index is **set aside** as ``*.corrupt`` and
  treated as a miss — never a crash, never a poisoned reload;
* entries recorded under different jax/jaxlib/mxnet_tpu versions are
  ignored (and age out via LRU), not deserialized;
* the directory is capped (``max_bytes``) with least-recently-used
  eviction at insert time.

Cache IO is best-effort by design: a read-only filesystem or a lost race
degrades to a recompile, never an error on the training/serving path.
"""
from __future__ import annotations

import contextlib as _contextlib
import hashlib
import json
import os
import threading
import time

__all__ = ["ProgramCache", "version_stamp"]

_INDEX = "index.json"
_INDEX_FORMAT = 1


def version_stamp():
    """The toolchain identity a compiled artifact is only valid for, and
    the layout of the parts its operations are named by: the key is taken
    with debug locations stripped, and a scope's name lives there
    (``telemetry.PARTS_VERSION``)."""
    import jax
    import jaxlib
    from .. import __version__ as mx_version
    from .. import telemetry
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "mxnet_tpu": mx_version, "parts": telemetry.PARTS_VERSION}


def _set_aside(path):
    """Move a damaged file out of the way instead of deleting evidence."""
    try:
        os.replace(path, path + ".corrupt")
    except OSError:
        try:
            os.remove(path)
        except OSError:
            pass


class ProgramCache:
    """LRU-bounded directory of compiled-program blobs.

    Thread-safe; every mutation rewrites ``index.json`` atomically.  All
    public methods are total: IO failure means miss (``get``) or no-op
    (``put``), never an exception on the caller's hot path.
    """

    def __init__(self, root, max_bytes=2 << 30):
        self.root = str(root)
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self.stats = {"hits": 0, "misses": 0, "puts": 0, "evictions": 0,
                      "corrupt": 0, "version_skips": 0}
        os.makedirs(self.root, exist_ok=True)

    @_contextlib.contextmanager
    def _fs_lock(self):
        """Inter-process exclusive lock around index read-modify-write:
        two workers sharing the default cache root (launch.py multi-worker,
        several servers warm-starting) must not clobber each other's index
        entries — a lost update strands blobs the LRU cap can no longer
        see.  Best-effort: where flock is unavailable, fall back to the
        thread lock alone."""
        fd = None
        try:
            try:
                import fcntl
                fd = os.open(os.path.join(self.root, ".lock"),
                             os.O_CREAT | os.O_RDWR)
                fcntl.flock(fd, fcntl.LOCK_EX)
            except (ImportError, OSError):
                fd = None
            yield
        finally:
            if fd is not None:
                try:
                    import fcntl
                    fcntl.flock(fd, fcntl.LOCK_UN)
                except (ImportError, OSError):
                    pass
                os.close(fd)

    # -- index -------------------------------------------------------------
    def _index_path(self):
        return os.path.join(self.root, _INDEX)

    def _load_index(self):
        """Read index.json; a corrupt one is set aside and replaced."""
        path = self._index_path()
        try:
            with open(path) as f:
                idx = json.load(f)
            if not isinstance(idx, dict) or \
                    idx.get("format") != _INDEX_FORMAT or \
                    not isinstance(idx.get("entries"), list):
                raise ValueError("bad index structure")
            return idx
        except ValueError:
            self.stats["corrupt"] += 1
            _set_aside(path)
        except OSError:
            pass
        return {"format": _INDEX_FORMAT, "entries": []}

    def _store_index(self, idx):
        path = self._index_path()
        try:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(idx, f, indent=1)
            os.replace(tmp, path)
        except OSError:
            pass

    # -- public ------------------------------------------------------------
    def get(self, key):
        """Blob bytes for ``key`` or None.  Verifies the content hash and
        the version stamp; any damage sets the entry aside as a miss."""
        from .. import faults as _faults
        try:
            # fault point: an injected load failure degrades to a miss —
            # the same recovery path as real cache damage (the get/put
            # contract stays total; docs/RESILIENCE.md)
            _faults.point("compile.cache_load")
        except _faults.FaultError:
            self.stats["misses"] += 1
            return None
        with self._lock, self._fs_lock():
            idx = self._load_index()
            entry = next((e for e in idx["entries"]
                          if e.get("key") == key), None)
            if entry is None:
                self.stats["misses"] += 1
                return None
            if entry.get("versions") != version_stamp():
                # stale toolchain: never deserialize a foreign executable
                self.stats["version_skips"] += 1
                self.stats["misses"] += 1
                return None
            path = os.path.join(self.root, entry.get("file", key + ".bin"))
            try:
                with open(path, "rb") as f:
                    blob = f.read()
            except OSError:
                blob = None
            if blob is None or \
                    hashlib.sha256(blob).hexdigest() != entry.get("sha256"):
                self.stats["corrupt"] += 1
                self.stats["misses"] += 1
                if blob is not None:
                    _set_aside(path)
                idx["entries"] = [e for e in idx["entries"]
                                  if e.get("key") != key]
                self._store_index(idx)
                return None
            # coarse LRU touch: skip the full index rewrite when the entry
            # was used recently — a hit should not cost O(entries) file IO
            # (a lost touch only degrades eviction order, never corrupts)
            if time.time() - float(entry.get("last_used", 0)) > 60.0:
                entry["last_used"] = time.time()
                entry["hits"] = int(entry.get("hits", 0)) + 1
                self._store_index(idx)
            self.stats["hits"] += 1
            return blob

    def put(self, key, blob, meta=None):
        """Insert a blob (atomic write), then evict LRU entries until the
        directory fits ``max_bytes`` again.  Returns True if stored."""
        blob = bytes(blob)
        record = {
            "key": key,
            "file": key + ".bin",
            "bytes": len(blob),
            "sha256": hashlib.sha256(blob).hexdigest(),
            "versions": version_stamp(),
            "meta": dict(meta or {}),
            "created": time.time(),
            "last_used": time.time(),
            "hits": 0,
        }
        with self._lock, self._fs_lock():
            path = os.path.join(self.root, record["file"])
            try:
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
            except OSError:
                return False
            idx = self._load_index()
            idx["entries"] = [e for e in idx["entries"]
                              if e.get("key") != key]
            idx["entries"].append(record)
            self._evict_locked(idx)
            self._store_index(idx)
            self.stats["puts"] += 1
            return True

    def _evict_locked(self, idx):
        """Drop least-recently-used entries until within the size cap."""
        total = sum(int(e.get("bytes", 0)) for e in idx["entries"])
        if total <= self.max_bytes:
            return
        by_age = sorted(idx["entries"],
                        key=lambda e: e.get("last_used", e.get("created", 0)))
        keep = list(by_age)
        for victim in by_age:
            if total <= self.max_bytes or len(keep) <= 1:
                break
            keep.remove(victim)
            total -= int(victim.get("bytes", 0))
            try:
                os.remove(os.path.join(self.root,
                                       victim.get("file", "")))
            except OSError:
                pass
            self.stats["evictions"] += 1
        order = {id(e): i for i, e in enumerate(idx["entries"])}
        idx["entries"] = sorted(keep, key=lambda e: order[id(e)])

    def invalidate(self, key):
        """Set a damaged-but-hash-clean entry aside (a blob that will not
        deserialize, e.g. a jaxlib rebuild at the same version string):
        the blob moves to ``*.corrupt`` and the index entry is dropped, so
        restarts stop re-paying a doomed load."""
        with self._lock, self._fs_lock():
            self.stats["corrupt"] += 1
            idx = self._load_index()
            entry = next((e for e in idx["entries"]
                          if e.get("key") == key), None)
            if entry is None:
                return
            _set_aside(os.path.join(self.root,
                                    entry.get("file", key + ".bin")))
            idx["entries"] = [e for e in idx["entries"]
                              if e.get("key") != key]
            self._store_index(idx)

    def entries(self):
        """Snapshot of the index records (for introspection/tests)."""
        with self._lock, self._fs_lock():
            return list(self._load_index()["entries"])

    def total_bytes(self):
        with self._lock, self._fs_lock():
            return sum(int(e.get("bytes", 0))
                       for e in self._load_index()["entries"])

    def clear(self):
        with self._lock, self._fs_lock():
            idx = self._load_index()
            for e in idx["entries"]:
                try:
                    os.remove(os.path.join(self.root, e.get("file", "")))
                except OSError:
                    pass
            self._store_index({"format": _INDEX_FORMAT, "entries": []})
