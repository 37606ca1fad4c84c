"""Persistent on-disk executable cache.

The paper measures compilation time as part of model load; here we
amortize it *across processes*: the ``"jit"``/``"pallas"`` targets lower
ahead-of-time via ``jax.jit(...).lower(...).compile()`` and the
resulting XLA executable is serialized (``jax.experimental.
serialize_executable``) under a key of ``structure_hash × weights ×
options × batch × jax-version × backend``.  A second process compiling
the same model loads the executable instead of re-running XLA.

Serialization is best-effort: any failure (old jax, cross-platform
blob, corrupt file) degrades to a normal compile — never to an error.
Entries are pickled, so the cache directory is trusted local state
(unlike ``repro.deserialize``, which must be safe on untrusted bytes);
point ``cache_dir``/``$REPRO_CACHE_DIR`` only at directories you own.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import pathlib
import pickle
import tempfile
from typing import Optional

import jax

_FORMAT_VERSION = 1

#: Env var capping the on-disk executable cache size in bytes; when set,
#: every ``store`` triggers an LRU sweep back under the cap.  Bucketed
#: executables multiply entries (one per batch bucket), so an unbounded
#: cache directory now grows much faster than it did pre-bucketing.
MAX_BYTES_ENV = "REPRO_CACHE_MAX_BYTES"


def cache_key(*parts: str) -> str:
    """Digest of the given parts plus everything environmental that
    invalidates an executable (jax version, backend platform)."""
    h = hashlib.sha256()
    for p in (f"v{_FORMAT_VERSION}", jax.__version__, jax.default_backend(),
              *parts):
        h.update(p.encode())
        h.update(b"\x00")
    return h.hexdigest()


#: JAX's own persistent compilation cache, when the environment names
#: none: one fixed path inside the checkout, so that every run from the
#: checkout finds what the runs before it compiled.
COMPILATION_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is changed; otherwise the cache goes to
    :data:`COMPILATION_CACHE_DIR`.  Entry points call this before their
    first compile; importing the package never does.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILATION_CACHE_DIR)
    return COMPILATION_CACHE_DIR


@contextlib.contextmanager
def compilation_cache_events():
    """Count the persistent compilation cache's hits and misses inside
    the block; yields ``{"hits": n, "misses": n}``, filled as it runs.
    A compile time measured over a hit is a cache load, not a compile."""
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}
    counts = {"hits": 0, "misses": 0}

    def listen(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listen)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_listener(listen)


def resolve_cache_dir(explicit: Optional[str]) -> Optional[str]:
    """Explicit option wins; else ``$REPRO_CACHE_DIR``; else disabled."""
    return explicit if explicit is not None else os.environ.get("REPRO_CACHE_DIR")


class ExecutableCache:
    """Pickle-per-entry directory cache of serialized XLA executables."""

    def __init__(self, root: str) -> None:
        self.root = root
        self.hits = 0
        self.misses = 0
        os.makedirs(root, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.root, f"{key}.xla")

    def load(self, key: str, devices):
        """Return a loaded executable, or None on miss/failure.

        ``devices`` are the devices the executable was compiled for, in
        its device-assignment order (one for an unsharded program, the
        mesh's for a sharded one).
        """
        path = self._path(key)
        if not os.path.exists(path):
            self.misses += 1
            return None
        try:
            from jax.experimental import serialize_executable as se
            with open(path, "rb") as f:
                payload = pickle.load(f)
            exe = se.deserialize_and_load(*payload,
                                          execution_devices=devices)
            self.hits += 1
            try:
                # LRU recency: a hit refreshes the entry's mtime so the
                # size-capped sweep evicts cold entries, not hot ones.
                os.utime(path)
            except OSError:
                pass
            return exe
        except Exception:
            # Corrupt/stale entry: drop it and recompile.
            try:
                os.remove(path)
            except OSError:
                pass
            self.misses += 1
            return None

    def store(self, key: str, compiled) -> bool:
        """Serialize ``compiled`` under ``key``; atomic via rename."""
        try:
            from jax.experimental import serialize_executable as se
            payload = se.serialize(compiled)
            blob = pickle.dumps(payload)
        except Exception:
            return False
        fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._path(key))
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        cap = os.environ.get(MAX_BYTES_ENV)
        if cap:
            try:
                prune(int(cap), self.root)
            except (OSError, ValueError):
                pass                       # the sweep is best-effort
        return True

    def stats(self) -> dict:
        """Hit/miss counters for this process plus the cache directory."""
        return {"dir": self.root, "hits": self.hits, "misses": self.misses}


def _load_manifests(root: str):
    """Map manifest path -> member ``.xla`` basenames, for every
    ``*.manifest.json`` a :class:`~repro.dist.ShardedExecutable` wrote
    next to its per-batch entries.  Unreadable manifests count as
    empty (and so get cleaned up as dangling)."""
    import json
    out = {}
    for name in os.listdir(root):
        if not name.endswith(".manifest.json"):
            continue
        path = os.path.join(root, name)
        members = []
        try:
            with open(path) as f:
                members = [f"{k}.xla" for k in json.load(f).get("members", [])]
        except (OSError, ValueError):
            pass
        out[path] = members
    return out


def prune(max_bytes: int, cache_dir: Optional[str] = None) -> dict:
    """Size-capped LRU sweep of the persistent executable cache.

    Deletes least-recently-used entries (mtime order — ``load``
    refreshes it on every hit) until the directory's entry bytes fit in
    ``max_bytes``, and clears out orphaned ``.tmp`` files from
    interrupted writes.  Corruption-safe by construction: entries are
    only ever whole files (writes go through an atomic rename), removal
    is whole-file, and a concurrently-vanishing file is skipped, so a
    reader racing the sweep sees either a valid entry or a clean miss —
    never a truncated one.

    Sharded executables group their per-batch artifacts under a
    ``*.manifest.json``; the sweep treats each group as ONE logical LRU
    entry — recency is the group's hottest member, eviction removes the
    members and the manifest together — so a pruned cache never holds a
    manifest pointing at missing artifacts (nor sharded artifacts with
    a dangling subset).  Manifests whose members are already all gone
    are removed as dangling up front.

    Returns ``{"dir", "before_bytes", "after_bytes", "removed"}``.
    """
    if max_bytes < 0:
        raise ValueError(f"max_bytes must be >= 0, got {max_bytes}")
    root = resolve_cache_dir(cache_dir)
    report = {"dir": root, "before_bytes": 0, "after_bytes": 0, "removed": 0}
    if not root or not os.path.isdir(root):
        return report
    manifests = _load_manifests(root)
    grouped = {m for members in manifests.values() for m in members}
    entries = []  # (mtime, size, [paths])  — one tuple per LRU unit
    for name in os.listdir(root):
        path = os.path.join(root, name)
        try:
            if name.endswith(".tmp"):      # orphaned partial write
                os.remove(path)
                report["removed"] += 1
                continue
            if (not name.endswith(".xla") or name in grouped
                    or not os.path.isfile(path)):
                continue
            st = os.stat(path)
        except OSError:
            continue                       # vanished mid-sweep: skip
        entries.append((st.st_mtime, st.st_size, [path]))
    for mpath, members in manifests.items():
        group, mtime, size = [], 0.0, 0
        for member in members:
            path = os.path.join(root, member)
            try:
                st = os.stat(path)
            except OSError:
                continue                   # member already gone
            group.append(path)
            mtime = max(mtime, st.st_mtime)
            size += st.st_size
        if not group:                      # dangling manifest: clean up
            try:
                os.remove(mpath)
                report["removed"] += 1
            except OSError:
                pass
            continue
        try:
            size += os.stat(mpath).st_size
        except OSError:
            pass
        entries.append((mtime, size, group + [mpath]))
    total = sum(size for _, size, _ in entries)
    report["before_bytes"] = total
    entries.sort()                         # oldest (coldest) first
    for _, size, paths in entries:
        if total <= max_bytes:
            break
        removed_any = False
        for path in paths:                 # group eviction is atomic:
            try:                           # members first, manifest last
                os.remove(path)
                removed_any = True
            except OSError:
                continue
        if not removed_any:
            continue
        total -= size
        report["removed"] += 1
    report["after_bytes"] = total
    return report


def open_cache(explicit_dir: Optional[str]) -> Optional[ExecutableCache]:
    """Open the executable cache at ``explicit_dir`` (or the resolved
    default root); returns ``None`` when caching is disabled or the
    directory cannot be created."""
    root = resolve_cache_dir(explicit_dir)
    if not root:
        return None
    try:
        return ExecutableCache(root)
    except OSError:
        return None
