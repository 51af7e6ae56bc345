"""Zero-cold-start plumbing: persistent compile cache + warmup bundles.

Two independent mechanisms, both optional and both silent-on-miss:

1. **Persistent compilation cache** — `enable_compile_cache()` turns on
   the process-wide JAX compilation cache at the directory
   ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.cache/jax-compile``.
   Every ``jax.jit`` compile in the process — train, serve, launch
   workers, bench — then reads/writes XLA executables on disk, so a
   respawned process recompiles nothing it has compiled before.

2. **Warmup bundles** — explicit AOT executables serialized with
   ``jax.experimental.serialize_executable`` into a zip written next to
   the checkpoint (``model.zip`` → ``model.zip.warm``), keyed by
   (version tag, executable key, device fingerprint, jax version) with
   sha256 integrity digests per entry (same idiom as the checkpoint
   serializer).  A fresh ``Engine.load()`` / ``DecodeEngine.load()``
   deserializes instead of compiling; ANY miss — absent file, corrupt
   entry, truncated zip, wrong tag, wrong device fingerprint, wrong jax
   version — falls back to compiling, never raises.  A missing bundle
   is silent (the normal first-run case); an unusable one logs exactly
   one warning.

The executables inside a bundle are device-committed: they only run on
the device set they were compiled for.  Callers route accordingly (see
``Engine._run_forward``).

Decode-engine bundle keys: the base set is ``prefill:<bucket>`` per
prompt bucket plus ``step`` / ``sample1`` / ``sample`` / ``reset`` /
``scrub``; the decode-side
optimizations add ``prefill_at:<bucket>`` (prefix cache AND chunked
prefill: prefill resuming at an offset), ``step_multi:<H>`` (fused
multi-step decode at horizon H — one entry per configured horizon),
and — when a draft model is configured — ``draft_prefill:<bucket>`` /
``draft_prefill_at:<bucket>`` / ``draft_step`` / ``draft_reset`` /
``draft_scrub`` plus the verification trio ``spec_step`` /
``propose`` / ``spec_accept``.  All of them ride the same
serialize/deserialize path, so speculative, prefix-cached and fused
engines warm-load compile-free too.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import warnings
import zipfile
from typing import Any, Dict, Optional, Sequence

import jax

#: jax's own variable: where it is set, jax has already read it and the
#: program sets no other directory in code
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the fixed fallback — never a temp name, pid or time, so a second run
#: of the same checkout finds what the first one compiled
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "jax-compile")
BUNDLE_FORMAT_VERSION = 1
BUNDLE_SUFFIX = ".warm"


def _harden_cache_writes() -> None:
    """Make jax's file-system compile-cache writes atomic.

    jax's LRU file cache ``put`` is a bare ``write_bytes`` (its
    filelock only engages when eviction is on), so a process killed
    mid-write — exactly what a preempted or chaos-killed fleet worker
    is — strands a HALF-WRITTEN executable that a later process
    deserializes as garbage and crashes on.  Route every put through
    write-to-temp + ``os.replace`` in the same directory: an entry is
    either absent or complete, never partial.  Identical concurrent
    writers are benign (same HLO key ⇒ same bytes; last rename wins).
    """
    from jax._src import lru_cache as _lru

    if getattr(_lru.LRUCache.put, "_dl4j_atomic", False):
        return

    def _atomic_put(self, key, val):
        if not key:
            raise ValueError("key cannot be empty")
        cache_path = self.path / f"{key}{_lru._CACHE_SUFFIX}"
        if cache_path.exists():
            return
        tmp = self.path / f"{key}.tmp.{os.getpid()}"
        tmp.write_bytes(val)
        os.replace(tmp, cache_path)

    _atomic_put._dl4j_atomic = True
    _lru.LRUCache.put = _atomic_put


def enable_compile_cache() -> str:
    """Use the JAX persistent compilation cache process-wide; returns
    the directory in effect.

    The directory is placed from OUTSIDE: where ``JAX_COMPILATION_CACHE_DIR``
    is set, jax read it at import and nothing here overrides it; where it
    is not, the cache is ``<checkout>/.cache/jax-compile`` (git-ignored)
    and the variable is exported so forked workers (``launch``) share it.
    Whether the cache is on at all stays jax's own switch
    (``JAX_ENABLE_COMPILATION_CACHE=false`` turns it off).  The
    min-compile-time threshold is dropped to 0 so even small executables
    persist.  Also starts the compile watch (obs/startup.py): what the
    cache saves and what it cannot is then counted.  Idempotent.
    """
    from ..obs.startup import watch_compiles

    watch_compiles()
    _harden_cache_writes()
    d = os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR
    if jax.config.jax_compilation_cache_dir != d:
        # the fallback — or a variable exported after jax was imported,
        # which jax has not seen yet; never a different directory
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    os.environ[CACHE_ENV] = d
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d


def device_fingerprint(mesh: Optional[Any] = None) -> str:
    """Identity of the device set an AOT executable is valid for.

    Serialized executables are XLA programs compiled for specific
    hardware; loading one on a different backend/topology is undefined.
    The fingerprint pins backend platform, device kind, device count,
    and the jax version that produced the serialization format.

    ``mesh`` (optional) appends a ``mesh(axis=size,...)`` component for
    executables compiled against a named mesh — sharded-decode programs
    are partitioned per mesh topology, so a bundle built on
    ``data=2`` must never load into a ``data=4`` (or unmeshed) process.
    Omitting it keeps the historical 4-field format, so single-device
    bundles stay loadable across this change.
    """
    devs = jax.devices()
    kind = devs[0].device_kind if devs else "none"
    parts = [jax.default_backend(), str(kind), str(len(devs)),
             jax.__version__]
    if mesh is not None:
        axes = ",".join(f"{n}={int(s)}"
                        for n, s in dict(mesh.shape).items())
        parts.append(f"mesh({axes})")
    return "|".join(parts)


def bundle_path_for(checkpoint_path: str) -> str:
    """Warmup-bundle path convention: next to the checkpoint zip."""
    return str(checkpoint_path) + BUNDLE_SUFFIX


def save_bundle(path: str, tag: str, entries: Dict[str, Any],
                mesh: Optional[Any] = None) -> str:
    """Serialize AOT ``entries`` ({key: compiled executable}) to ``path``.

    Zip layout mirrors the checkpoint serializer: a ``meta.json``
    carrying tag / device fingerprint / jax version / key mapping /
    per-entry sha256 integrity digests, plus one pickled
    ``(payload, in_tree, out_tree)`` blob per executable.  Written
    atomically (tmp + rename) so a crash mid-save never leaves a
    half-bundle where a valid one was.  ``mesh``: pass the named mesh
    the executables were partitioned over (sharded decode) so the
    fingerprint pins its topology; None for single-device programs.
    """
    from jax.experimental import serialize_executable as _se

    names: Dict[str, str] = {}
    blobs: Dict[str, bytes] = {}
    for i, key in enumerate(sorted(entries)):
        try:
            payload, in_tree, out_tree = _se.serialize(entries[key])
        except jax.errors.JaxRuntimeError as e:
            # a backend limit, not a bundle bug: XLA:CPU cannot serialize
            # a sort comparator (the samplers' top-k); the TPU client can
            raise RuntimeError(
                f"warmup bundle entry {key!r} is not serializable on the "
                f"{jax.default_backend()} backend: {e}") from e
        ename = f"exec_{i}.bin"
        names[ename] = key
        blobs[ename] = pickle.dumps((payload, in_tree, out_tree))
    meta = {
        "format_version": BUNDLE_FORMAT_VERSION,
        "tag": tag,
        "fingerprint": device_fingerprint(mesh),
        "jax_version": jax.__version__,
        "entries": names,
        "integrity": {e: hashlib.sha256(b).hexdigest() for e, b in blobs.items()},
    }
    tmp = str(path) + ".tmp"
    with zipfile.ZipFile(tmp, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr("meta.json", json.dumps(meta, indent=2))
        for ename, blob in blobs.items():
            z.writestr(ename, blob)
    os.replace(tmp, path)
    return str(path)


class _BundleMiss(Exception):
    """Internal: a specific reason the bundle can't be used."""


def load_bundle(path: Optional[str], tag: Optional[str] = None,
                mesh: Optional[Any] = None,
                devices: Optional[Sequence[Any]] = None) -> Dict[str, Any]:
    """Load a warmup bundle; return {} on ANY miss, never raise.

    An absent file is the normal cold-start case and stays silent.  An
    existing-but-unusable bundle (truncated/corrupt zip, integrity or
    fingerprint or tag or jax-version mismatch, undeserializable entry)
    emits exactly one ``RuntimeWarning`` naming the reason, then returns
    {} so the caller compiles as if no bundle existed.  ``mesh`` must
    match what the bundle was saved with (the fingerprint carries the
    mesh topology component) — a differently-meshed bundle falls back
    to compile under the same one-warning contract.

    ``devices``: the devices the executables will run on — by default
    the mesh's, else the first local device (the serving engines' lead
    device).  Without it jax loads each executable for EVERY visible
    device, and a one-device program then refuses its one-shard
    arguments on any host with more than one device.
    """
    if not path or not os.path.exists(path):
        return {}
    if devices is None:
        devices = (list(mesh.devices.flat) if mesh is not None
                   else jax.local_devices()[:1])
    from jax.experimental import serialize_executable as _se

    try:
        with zipfile.ZipFile(path, "r") as z:
            meta = json.loads(z.read("meta.json"))
            if meta.get("format_version") != BUNDLE_FORMAT_VERSION:
                raise _BundleMiss(
                    f"format_version {meta.get('format_version')!r}"
                )
            if tag is not None and meta.get("tag") != tag:
                raise _BundleMiss(f"tag {meta.get('tag')!r} != wanted {tag!r}")
            if meta.get("jax_version") != jax.__version__:
                raise _BundleMiss(
                    f"jax {meta.get('jax_version')!r} != {jax.__version__!r}"
                )
            fp = device_fingerprint(mesh)
            if meta.get("fingerprint") != fp:
                raise _BundleMiss(
                    f"device fingerprint {meta.get('fingerprint')!r} != {fp!r}"
                )
            integrity = meta.get("integrity", {})
            out: Dict[str, Any] = {}
            for ename, key in meta.get("entries", {}).items():
                blob = z.read(ename)
                if integrity.get(ename) != hashlib.sha256(blob).hexdigest():
                    raise _BundleMiss(f"integrity mismatch on {ename}")
                payload, in_tree, out_tree = pickle.loads(blob)
                out[key] = _se.deserialize_and_load(
                    payload, in_tree, out_tree,
                    execution_devices=list(devices))
            return out
    except Exception as exc:  # noqa: BLE001 — fallback-to-compile contract:
        # any unusable bundle must degrade to a cold compile, not an error.
        warnings.warn(
            f"warmup bundle {path!r} unusable ({exc!r}); falling back to "
            "compile",
            RuntimeWarning,
            stacklevel=2,
        )
        return {}
