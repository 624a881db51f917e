"""Warm-artifact store: persisted AOT executables for cold-start resilience.

The persistent XLA compilation cache (``utils/compile_cache``) replays
*compiles* across processes, but a respawned replica still pays tracing,
lowering, and cache lookup per fused program — and an explicit CPU run
has no default cache.  This layer goes one level higher: after a fused
program compiles, the finished executable is serialized via JAX's AOT
path (``jax.experimental.serialize_executable``) and persisted next to
the model artifact; a kill -9 → respawn replica (or a second process in a
rolling deploy) deserializes the executable in milliseconds instead of
recompiling for seconds.

Entries are keyed by ``(kernel id, bucket rung, mesh shape, dtype)``
under a per-``fingerprint()`` directory — the fingerprint pins the jax /
jaxlib versions, backend, device kind and device count, so an upgraded
wheel or a different topology can never replay a stale executable.  Every
entry is written with the model-artifact sidecar-commit CRC scheme
(``serve/integrity``) using per-writer tmp names: N replicas warming the
same ladder concurrently coordinate by write-to-tmp + atomic rename,
last writer wins.  A torn write, corrupt entry, fingerprint mismatch, or
deserialization failure — or a replayed executable that fails when called
— is *detected* and degrades to a plain recompile: a reason-coded
``warmstart.degraded.<reason>`` counter plus a flight event, never a wrong
answer and never a crash.

Observability: ``warmstart.hits`` / ``misses`` / ``saves`` /
``save_failures`` / ``degraded`` (+ per-reason) / ``compile_skips`` /
``gc_evictions`` counters; fault points ``warmstart.load`` and
``warmstart.save`` (``fault/injection``) exercise both degrade paths in
chaos runs.  ``deploy()`` seals a ``manifest.json`` after pre-warming the
bucket ladder so an inheriting replica can see what is already warm.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import shutil
import threading
from typing import Optional

from flink_ml_tpu.utils import knobs

__all__ = [
    "ENTRY_FORMAT",
    "WarmstartStore",
    "active",
    "activate_for",
    "configure",
    "enabled",
    "fingerprint",
    "store_dir_for",
]

#: bump when the pickled entry layout changes — old entries degrade to
#: recompile instead of unpickling garbage (2: entries record the ids of
#: the devices the executable was compiled for)
ENTRY_FORMAT = 2

_LOCK = threading.Lock()
_STORE: Optional["WarmstartStore"] = None
_FINGERPRINT: Optional[str] = None


def enabled() -> bool:
    """Whether the warm-artifact layer may activate at all."""
    return knobs.knob_bool("FMT_WARMSTART")


def fingerprint() -> str:
    """Digest pinning everything an executable is only valid under:
    jax/jaxlib versions, backend name, device kind, and device count.
    A mismatch on any axis means the entry must not be replayed."""
    global _FINGERPRINT
    if _FINGERPRINT is not None:
        return _FINGERPRINT
    import jax

    try:
        import jaxlib

        jaxlib_ver = getattr(jaxlib, "__version__", "")
    except ImportError:
        jaxlib_ver = ""
    try:
        devs = jax.devices()
        parts = (
            jax.__version__,
            jaxlib_ver,
            jax.default_backend(),
            devs[0].device_kind if devs else "",
            str(len(devs)),
        )
    except Exception:  # backend init failure: never break the caller
        parts = (jax.__version__, jaxlib_ver, "unknown", "", "0")
    _FINGERPRINT = hashlib.sha256("|".join(parts).encode()).hexdigest()[:12]
    return _FINGERPRINT


def store_dir_for(model_path: str) -> str:
    """The default warm-artifact directory for a model artifact: a
    ``warm_aot/`` directory right beside the model's own files, so the
    artifact and its executables travel (and get cleaned up) together."""
    return os.path.join(model_path, "warm_aot")


def configure(root: Optional[str]) -> Optional["WarmstartStore"]:
    """(De)activate the process-wide store.  ``None`` deactivates."""
    global _STORE
    with _LOCK:
        if root is None:
            _STORE = None
        elif _STORE is None or _STORE.root != root:
            _STORE = WarmstartStore(root)
        return _STORE


def activate_for(model_path: str) -> Optional["WarmstartStore"]:
    """Activate the store a deploy of ``model_path`` should use:
    ``FMT_WARM_DIR`` when set (a fleet-shared store), else ``warm_aot/``
    beside the artifact.  No-op (returns None) when the layer is off."""
    if not enabled():
        return None
    return configure(knobs.knob_str("FMT_WARM_DIR")
                     or store_dir_for(model_path))


def inherited_manifest_entries(model_path: str) -> int:
    """How many warm artifacts a replica booting from ``model_path`` will
    inherit (0 = a cold boot): the sealed manifest's entry count at the
    store that replica will activate.  Never raises — this is a status
    annotation, not a gate."""
    if not enabled():
        return 0
    try:
        root = knobs.knob_str("FMT_WARM_DIR") or store_dir_for(model_path)
        return len(WarmstartStore(root).manifest().get("entries", {}))
    except Exception:
        return 0


def active() -> Optional["WarmstartStore"]:
    """The currently configured store, or None (layer fully inert)."""
    global _STORE
    if not enabled():
        return None
    with _LOCK:
        if _STORE is None:
            env_dir = knobs.knob_str("FMT_WARM_DIR")
            if env_dir:
                # a spawned replica inherits the incumbent's store via env
                _STORE = WarmstartStore(env_dir)
        return _STORE


def _degrade(reason: str, key: str, path: str, err: object) -> None:
    """Reason-coded degrade: counter + flight event, caller recompiles."""
    from flink_ml_tpu import obs

    obs.counter_add("warmstart.degraded")
    obs.counter_add(f"warmstart.degraded.{reason}")
    obs.flight.record(
        "warmstart.degraded", reason=reason, key=key, path=path,
        error=str(err)[:200],
    )


class WarmstartStore:
    """One warm-artifact directory: ``<root>/<fingerprint>/<digest>.aot``
    entries with CRC commit sidecars, plus a sealed ``manifest.json``."""

    def __init__(self, root: str):
        self.root = root
        self.fingerprint = fingerprint()
        self._dir = os.path.join(root, self.fingerprint)
        self._lock = threading.Lock()
        self._manifest_keys: dict = {}

    # -- keys and paths -------------------------------------------------

    @staticmethod
    def entry_key(kernel: str, bucket: int, mesh: int, dtype: str,
                  extra: str = "") -> str:
        """The logical identity of one executable: which fused plan
        (``kernel`` — serve name + structural token), which ladder rung,
        which mesh width, which precision; ``extra`` carries the
        argument shape/treedef digest that pins feature dims."""
        return f"{kernel}|b{int(bucket)}|m{int(mesh)}|{dtype}|{extra}"

    def entry_path(self, key: str) -> str:
        digest = hashlib.sha256(key.encode()).hexdigest()[:20]
        return os.path.join(self._dir, digest + ".aot")

    def note_degraded(self, reason: str, err: object) -> None:
        """Count a degrade a CALLER of the store absorbed (a dispatch
        surface that fell back to its plain jitted program) — the same
        counter + flight event as the store's own, so no degrade is
        silent."""
        _degrade(reason, "", self.root, err)

    # -- load / save ----------------------------------------------------

    def load(self, key: str, recompile=None):
        """The replayed executable for ``key``, or None (miss or
        detected-degrade — the caller compiles as if the store were
        absent; this function never raises and never returns a wrong
        executable).  The executable is loaded onto exactly the devices
        it was compiled for.  ``recompile()`` hands back the freshly
        jitted program: a replayed executable that fails when CALLED
        degrades to it (:class:`_Replayed`) instead of failing the
        request."""
        from flink_ml_tpu import obs
        from flink_ml_tpu.fault import injection
        from flink_ml_tpu.serve.errors import ModelIntegrityError
        from flink_ml_tpu.serve.integrity import verify_commit_record

        path = self.entry_path(key)
        try:
            injection.maybe_fail("warmstart.load")
            if not os.path.exists(path):
                obs.counter_add("warmstart.misses")
                return None
            if not os.path.exists(path + ".commit.json"):
                # a torn write: the entry renamed in but the writer died
                # before committing the sidecar (or a last-writer race
                # left them out of step — the CRC path below covers that)
                raise _Torn(f"{path!r} has no commit record")
            verify_commit_record(path, required=True)
            with open(path, "rb") as f:
                blob = pickle.loads(f.read())
            if (not isinstance(blob, dict)
                    or blob.get("fmt") != ENTRY_FORMAT
                    or blob.get("key") != key):
                raise _Format(f"entry {path!r} has an unexpected layout")
            if blob.get("fingerprint") != self.fingerprint:
                raise _Fingerprint(
                    f"entry {path!r} was built under fingerprint "
                    f"{blob.get('fingerprint')!r}, this process is "
                    f"{self.fingerprint!r}"
                )
            import jax
            from jax.experimental import serialize_executable as se

            # without execution_devices jax loads onto EVERY device of the
            # backend, and a one-device executable then refuses its call
            # in any process that owns more ("Expected args ... to have 8
            # shards, got: [1, 1]")
            by_id = {d.id: d for d in jax.devices()}
            loaded = se.deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"],
                execution_devices=[by_id[i] for i in blob["device_ids"]],
            )
        except injection.InjectedFault as e:
            _degrade("injected", key, path, e)
            return None
        except _Torn as e:
            _degrade("torn", key, path, e)
            return None
        except _Fingerprint as e:
            _degrade("fingerprint", key, path, e)
            return None
        except ModelIntegrityError as e:
            _degrade("corrupt", key, path, e)
            return None
        except _Format as e:
            _degrade("format", key, path, e)
            return None
        except Exception as e:  # unpickle/deserialize failure, I/O, ...
            _degrade("deserialize", key, path, e)
            return None
        obs.counter_add("warmstart.hits")
        return _Replayed(loaded, key, path, recompile)

    def save(self, key: str, compiled) -> bool:
        """Persist ``compiled`` (a ``jax.stages.Compiled``) under ``key``.
        Returns False on any failure (counter + flight event) — a replica
        that cannot persist its executable still serves; the next process
        just compiles again."""
        from flink_ml_tpu import obs
        from flink_ml_tpu.fault import injection
        from flink_ml_tpu.serve.integrity import AtomicFile

        path = self.entry_path(key)
        try:
            injection.maybe_fail("warmstart.save")
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            blob = pickle.dumps({
                "fmt": ENTRY_FORMAT,
                "fingerprint": self.fingerprint,
                "key": key,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
                "device_ids": [
                    d.id
                    for d in compiled.runtime_executable().local_devices()
                ],
            })
            with AtomicFile(path, unique_tmp=True) as f:
                f.write(blob)
        except injection.InjectedFault as e:
            obs.counter_add("warmstart.save_failures")
            obs.flight.record("warmstart.save_failed", key=key, path=path,
                              error=str(e)[:200])
            return False
        except Exception as e:
            obs.counter_add("warmstart.save_failures")
            obs.flight.record("warmstart.save_failed", key=key, path=path,
                              error=str(e)[:200])
            return False
        obs.counter_add("warmstart.saves")
        with self._lock:
            self._manifest_keys[key] = os.path.basename(path)
        self.gc()
        return True

    # -- manifest -------------------------------------------------------

    def manifest_path(self) -> str:
        return os.path.join(self._dir, "manifest.json")

    def seal_manifest(self) -> Optional[str]:
        """Atomically write the manifest of everything this process has
        warmed (deploy calls this after walking the ladder).  Entries
        observed on disk from other writers are folded in — the manifest
        describes the store, not one process's contribution."""
        from flink_ml_tpu.serve.integrity import atomic_json_dump

        try:
            entries = dict(self._read_manifest().get("entries", {}))
        except Exception:
            entries = {}
        with self._lock:
            entries.update(self._manifest_keys)
        try:
            os.makedirs(self._dir, exist_ok=True)
            mp = self.manifest_path()
            atomic_json_dump({
                "fingerprint": self.fingerprint,
                "format": ENTRY_FORMAT,
                "entries": entries,
            }, mp)
        except OSError:
            return None
        return mp

    def _read_manifest(self) -> dict:
        try:
            with open(self.manifest_path()) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def manifest(self) -> dict:
        """The sealed manifest (empty dict when none is on disk)."""
        return self._read_manifest()

    # -- bounded-size GC ------------------------------------------------

    def gc(self, max_bytes: Optional[int] = None) -> int:
        """Bound the store's on-disk size.  Stale-fingerprint directories
        (an upgraded jax wheel left them unreadable forever) are evicted
        first, then oldest-mtime entries under the live fingerprint.
        Returns the number of evicted files/directories; never raises."""
        from flink_ml_tpu import obs

        if max_bytes is None:
            max_bytes = knobs.knob_int("FMT_WARM_CACHE_MB") * (1 << 20)
        evicted = 0
        try:
            total = 0
            stale_dirs, live_files = [], []
            for name in sorted(os.listdir(self.root)):
                p = os.path.join(self.root, name)
                if not os.path.isdir(p):
                    continue
                size = sum(
                    os.path.getsize(os.path.join(p, f))
                    for f in os.listdir(p)
                    if os.path.isfile(os.path.join(p, f))
                )
                total += size
                if name != self.fingerprint:
                    stale_dirs.append((p, size))
                else:
                    live_files = sorted(
                        (os.path.getmtime(os.path.join(p, f)),
                         os.path.join(p, f),
                         os.path.getsize(os.path.join(p, f)))
                        for f in os.listdir(p)
                        if f.endswith(".aot")
                    )
            for p, size in stale_dirs:
                if total <= max_bytes:
                    break
                shutil.rmtree(p, ignore_errors=True)
                total -= size
                evicted += 1
            for _, f, size in live_files:
                if total <= max_bytes:
                    break
                for victim in (f, f + ".commit.json"):
                    try:
                        os.remove(victim)
                    except OSError:
                        pass
                total -= size
                evicted += 1
        except OSError:
            return evicted
        if evicted:
            obs.counter_add("warmstart.gc_evictions", evicted)
        return evicted


class _Replayed:
    """A store-loaded executable whose CALL can degrade too.

    Loading proves the bytes deserialize, not that the executable accepts
    this process's arguments (a layout or device-set disagreement shows
    only at call time).  The first failing call counts a
    ``warmstart.degraded.call``, switches to ``recompile()``'s program for
    good, and answers the request from it.  Allocator exhaustion is not
    the artifact's fault and re-raises for the pressure layer, as does a
    failure after donated arguments were consumed (nothing left to retry
    with)."""

    def __init__(self, loaded, key: str, path: str, recompile):
        self._fn = loaded
        self._key = key
        self._path = path
        self._recompile = recompile

    def __call__(self, *args):
        try:
            return self._fn(*args)
        except Exception as e:
            import jax

            from flink_ml_tpu.fault.pressure import is_oom

            if self._recompile is None or is_oom(e) or any(
                getattr(x, "is_deleted", lambda: False)()
                for x in jax.tree_util.tree_leaves(args)
            ):
                raise
            _degrade("call", self._key, self._path, e)
            self._fn, self._recompile = self._recompile(), None
            return self._fn(*args)


class _Torn(RuntimeError):
    """Entry present without its commit sidecar — a torn write."""


class _Fingerprint(RuntimeError):
    """Entry built under a different jax/backend fingerprint."""


class _Format(RuntimeError):
    """Entry blob has an unexpected pickled layout."""
