"""Unified compiled-program registry + persistent compile cache.

The stack compiles XLA programs at six independent sites — executor
forward jits, the fused train step, the serve bucket ladder, decode
prefill/slot programs, gluon CachedOp modes, and quantize calibration
executors — and before this module each kept its own dict cache, so a
freshly spawned serve replica or resumed trainer recompiled its entire
ladder from scratch. This module is the one cache they all stand
behind:

1. **Registry** — :func:`get_or_build` keyed by a stable
   :class:`ProgramKey` fingerprint (graph/symbol hash, input
   shapes+dtypes, sharding/mesh, donation layout, numerics mode, and a
   jax+library **version salt**). Within a process, two sites that
   build the same program share ONE jitted callable — a hot-swap
   replacement engine re-warms its whole bucket ladder as in-memory
   cache hits. The registry is bounded (``MXNET_PROGRAMS_MAX``, LRU)
   with eviction telemetry, and every entry records its build wall,
   compile/disk-hit counts observed inside the build callable (sites
   that return lazily-jitted callables compile at first invocation
   instead — the prewarm report and the global compile/disk-hit split
   are the cold-start measurement), and (when a site attaches one) the
   program's XLA cost-analysis record from ``health.capture_cost``.

2. **Persistent compile cache** — JAX's persistent compilation cache
   is always on, so a compile in a FRESH process deserializes the
   executable from disk instead of running XLA. Its directory is
   placed from OUTSIDE the program: where ``JAX_COMPILATION_CACHE_DIR``
   is set JAX picks it up itself and no code sets a directory; where
   it is not, :func:`configure_compile_cache` (called once at ``import
   mxnet_tpu``, before anything can compile) points it at the fixed
   ``<checkout>/.jax_cache``. The path is part of the cache key, so it
   never moves: no temp, pid or time component.
   Telemetry distinguishes the two honestly: a disk load still counts
   as a compile *request* (``jit/backend_compile_total`` — every
   zero-recompile assertion keeps meaning "zero traces"), while
   ``programs/compile_total`` vs ``programs/disk_hits_total`` split
   real backend compiles from cache loads.

3. **Warm-set manifest** — each registered program appends its
   fingerprint + abstract input spec to ``<dir>/warmset.json``
   (written through :func:`checkpoint.atomic_writer`, so the file is
   never torn). :func:`prewarm` replays those specs at startup through
   per-kind replay callables, so a new replica compiles its whole
   ladder from disk before ``/healthz`` goes ready —
   ``InferenceEngine.warmup()`` and ``DecodeEngine`` warmup route
   through it. Entries whose version salt mismatches are skipped with
   a warning (never replayed as wrong traces); a corrupt or torn
   manifest degrades to a cold compile, never a crash.

4. **Donated-loop warmup rule** — :func:`warm_twice` centralizes the
   pjit sharding-provenance discipline (one executable per input
   provenance; warm on the executing thread; assert from step 2) that
   DecodeEngine's two-pass warmup discovered, so the next subsystem
   doesn't rediscover the bug.

Knobs: ``JAX_COMPILATION_CACHE_DIR`` (JAX's own),
``MXNET_PROGRAMS_MAX`` (config.py).
Docs: docs/compile_cache.md. Fresh-process gate: tests/test_programs.py.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import os
import threading
from collections import OrderedDict

from .base import MXNetError

__all__ = ["ProgramKey", "fingerprint", "graph_hash", "version_salt",
           "get_or_build", "attach_cost", "prewarm", "warm_twice",
           "next_instance", "configure_compile_cache", "cache_dir",
           "warmset_path", "load_warmset", "note_warm", "stats",
           "entries", "reset", "WARMSET_FORMAT"]

_log = logging.getLogger(__name__)

WARMSET_FORMAT = 1

_lock = threading.RLock()
_entries = OrderedDict()        # fingerprint -> _Entry (LRU order)
_build_locks = {}               # fingerprint -> Lock (never removed; tiny)
_warmset_lock = threading.Lock()
_warmset_seen = set()           # (path, fp) known recorded: skip the RMW
_instance_seq = [0]
_salt_cache = [None]


def _tm():
    from . import telemetry
    return telemetry


def _config(name, default=None):
    from .config import get
    return get(name, default)


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def version_salt():
    """Library/backend salt folded into every fingerprint: a warm-set
    manifest (or registry entry) written by a different jax/jaxlib/
    framework version or backend must never be replayed as if it named
    the same executable. Device count rides along — XLA_FLAGS device
    topology changes the compiled program."""
    if _salt_cache[0] is not None:
        return _salt_cache[0]
    from .libinfo import __version__
    parts = ["mxnet=%s" % __version__]
    try:
        import jax
        import jaxlib
        parts.append("jax=%s" % jax.__version__)
        parts.append("jaxlib=%s" % jaxlib.__version__)
        try:
            parts.append("backend=%s" % jax.default_backend())
            parts.append("devices=%d" % jax.device_count())
            # device count alone cannot distinguish 2 processes x 1
            # device from 1 process x 2 devices — same SPMD partition,
            # different runtime (cross-host collectives) — so the
            # process count is salted explicitly: a dist_tpu_sync
            # worker must never replay a single-host manifest entry as
            # if it named the same executable
            parts.append("processes=%d" % jax.process_count())
        except Exception:
            parts.append("backend=uninitialized")
    except Exception:
        parts.append("jax=unavailable")
    _salt_cache[0] = ";".join(parts)
    return _salt_cache[0]


def invalidate_version_salt():
    """Drop the memoized salt.  The elastic rescale path calls this
    after a shutdown→reinit cycle: the salt embeds ``processes=N`` and
    the device topology, both of which just changed — programs built
    for the new world must re-fingerprint (and hit the persistent
    compile cache on disk, not replay a stale executable)."""
    _salt_cache[0] = None


def graph_hash(obj):
    """Stable graph fingerprint component. Accepts a Symbol (hashes its
    json), a string (hashed as-is), or any JSON-able structure."""
    if hasattr(obj, "tojson"):
        payload = obj.tojson()
    elif isinstance(obj, str):
        payload = obj
    else:
        payload = json.dumps(obj, sort_keys=True, default=str)
    return hashlib.sha1(payload.encode()).hexdigest()[:16]


def _canonical(spec):
    return json.dumps(spec, sort_keys=True, default=str)


class ProgramKey(object):
    """Identity of one compiled program in the registry.

    ``kind``
        The jit site (``executor_forward``, ``fused_step``,
        ``serve_bucket``, ``decode_prefill``, ``decode_step``,
        ``cachedop``, ``calib_executor``, ...).
    ``graph``
        Graph/symbol hash (:func:`graph_hash`) — what is computed.
    ``spec``
        JSON-able dict of everything else that specializes the
        executable: input shapes+dtypes, sharding/mesh signature,
        donation layout, numerics mode, bucket sizes. This is also the
        abstract input spec the warm-set manifest stores for replay.
    ``instance``
        Optional per-object salt for sites whose built value captures
        live Python state (CachedOp blocks close over parameter
        identity; calibration executors hold written weights) and must
        therefore NOT be shared across instances. Instance-salted
        entries still land in the warm-set for accounting, but carry
        no cross-process identity.
    """

    __slots__ = ("kind", "graph", "spec", "instance", "_fp")

    def __init__(self, kind, graph, spec=None, instance=None):
        self.kind = str(kind)
        self.graph = str(graph)
        self.spec = spec if spec is not None else {}
        self.instance = None if instance is None else str(instance)
        self._fp = None

    @property
    def fingerprint(self):
        if self._fp is None:
            h = hashlib.sha256()
            for part in (self.kind, self.graph, _canonical(self.spec),
                         self.instance or "", version_salt()):
                h.update(part.encode())
                h.update(b"\x00")
            self._fp = h.hexdigest()[:32]
        return self._fp

    def __repr__(self):
        return "ProgramKey(%s, %s, %s)" % (self.kind, self.graph,
                                           self.fingerprint)


def fingerprint(kind, graph, spec=None, instance=None):
    """Fingerprint without constructing a key (manifest tooling)."""
    return ProgramKey(kind, graph, spec, instance).fingerprint


def next_instance(prefix):
    """Process-unique instance salt (``prefix:N``) for sites whose
    built values must not be shared across objects. Never key by
    ``id(obj)`` — CPython reuses addresses after GC."""
    with _lock:
        _instance_seq[0] += 1
        return "%s:%d" % (prefix, _instance_seq[0])


# ---------------------------------------------------------------------------
# persistent compile cache wiring
# ---------------------------------------------------------------------------

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure_compile_cache():
    """Place the persistent compile cache, once, at ``import
    mxnet_tpu``: nothing when ``JAX_COMPILATION_CACHE_DIR`` already
    placed it from outside, else the fixed in-checkout directory. The
    min-compile-time and min-entry-size gates are zeroed either way so
    every program is cached, not just the slow ones — parameter init
    and bind-time fills compile dozens of tiny eager programs."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def cache_dir():
    """The resolved persistent-cache directory (``warmset.json`` and
    the forensics reports live under it)."""
    import jax
    return os.path.abspath(jax.config.jax_compilation_cache_dir)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class _Entry(object):
    __slots__ = ("key", "value", "build_s", "compile_requests",
                 "disk_hits", "uses", "cost")

    def __init__(self, key, value, build_s, compile_requests, disk_hits):
        self.key = key
        self.value = value
        self.build_s = build_s
        self.compile_requests = compile_requests
        self.disk_hits = disk_hits
        self.uses = 1
        self.cost = None


def max_entries():
    """Registry LRU bound (``MXNET_PROGRAMS_MAX``; 0 = unbounded)."""
    try:
        return int(_config("MXNET_PROGRAMS_MAX"))
    except Exception:
        return 512


def get_or_build(key, build_fn, retain=True):
    """The one compiled-program cache API every jit site stands behind.

    Returns the registered value for ``key`` (a :class:`ProgramKey`),
    building it with ``build_fn()`` on first sight. Builds are
    serialized per fingerprint (two engines warming the same ladder
    concurrently build each program once), measured (wall, compile
    requests, persistent-cache disk hits — thread-local attribution,
    so concurrent unrelated builds don't cross-count; note the bracket
    covers ``build_fn`` only, so a site returning a lazily-jitted
    callable attributes its compile to the first invocation — the
    prewarm report — not the entry), recorded in the warm-set manifest, and bounded by
    ``MXNET_PROGRAMS_MAX`` with LRU eviction telemetry.

    ``retain=False`` measures and counts the build but does NOT store
    the value: for site values that pin live state (a calibration
    executor holds the model's written weights on device) the caller's
    own cache stays the only owner, so the registry never extends
    their lifetime.
    """
    fp = key.fingerprint
    tm = _tm()
    with _lock:
        e = _entries.get(fp)
        if e is not None:
            _entries.move_to_end(fp)
            e.uses += 1
            if tm._enabled:
                tm.counter("programs/registry_hits_total",
                           "get_or_build calls served from the "
                           "compiled-program registry").inc()
            return e.value
        block = _build_locks.get(fp)
        if block is None:
            block = _build_locks[fp] = threading.Lock()
    try:
        with block:
            with _lock:
                e = _entries.get(fp)
                if e is not None:       # built while we waited
                    _entries.move_to_end(fp)
                    e.uses += 1
                    return e.value
            if tm._enabled:
                tm._ensure_compile_listener()
            t0 = tm.monotonic()
            c0, d0 = tm.thread_compile_stats()
            value = build_fn()
            c1, d1 = tm.thread_compile_stats()
            e = _Entry(key, value, tm.monotonic() - t0, c1 - c0,
                       d1 - d0)
            evicted = 0
            if retain:
                with _lock:
                    _entries[fp] = e
                    cap = max_entries()
                    while cap > 0 and len(_entries) > cap:
                        _entries.popitem(last=False)
                        evicted += 1
            if tm._enabled:
                tm.counter("programs/registered_total",
                           "Programs built and registered in the "
                           "compiled-program registry", ("kind",)
                           ).labels(key.kind).inc()
                tm.histogram("programs/build_seconds",
                             "Wall time of one registry program build "
                             "(trace + lower + compile or disk load)"
                             ).observe(e.build_s)
                if evicted:
                    tm.counter("programs/evictions_total",
                               "Registry entries evicted past "
                               "MXNET_PROGRAMS_MAX (LRU)").inc(evicted)
            _append_warmset(key)
            return value
    finally:
        # the per-fingerprint build lock has done its job once the
        # entry exists (or the build failed): drop it so instance-
        # salted keys can't grow the lock table without bound
        with _lock:
            _build_locks.pop(fp, None)


def attach_cost(key, rec):
    """Alias a ``health.capture_cost`` record onto the registry entry
    for ``key`` (sites capture cost with live args the registry never
    sees; the alias makes ``entries()`` a one-stop program table)."""
    fp = key.fingerprint if isinstance(key, ProgramKey) else str(key)
    with _lock:
        e = _entries.get(fp)
        if e is not None:
            e.cost = rec
    return rec


def entries():
    """Snapshot of the registry: {fingerprint: row-dict}, LRU order
    (oldest first) — surfaced by ``mxnet_tpu.diagnostics()``."""
    out = OrderedDict()
    with _lock:
        rows = list(_entries.items())
    for fp, e in rows:
        row = {"kind": e.key.kind, "graph": e.key.graph,
               "build_s": round(e.build_s, 4),
               "compile_requests": e.compile_requests,
               "disk_hits": e.disk_hits, "uses": e.uses}
        if e.cost:
            row["gflops"] = round(e.cost.get("flops", 0.0) / 1e9, 3)
        out[fp] = row
    return out


def stats():
    """Registry totals for diagnostics and bug reports."""
    with _lock:
        rows = list(_entries.values())
    return {"entries": len(rows),
            "build_s_total": round(sum(e.build_s for e in rows), 3),
            "compile_requests": sum(e.compile_requests for e in rows),
            "disk_hits": sum(e.disk_hits for e in rows),
            "cache_dir": cache_dir()}


def reset():
    """Drop every registry entry (test isolation). Site-local memos
    keep already-built programs alive; the registry simply re-registers
    on next sight."""
    with _lock:
        _entries.clear()
    _warmset_seen.clear()


# ---------------------------------------------------------------------------
# warm-set manifest
# ---------------------------------------------------------------------------

def warmset_path(directory=None):
    return os.path.join(directory or cache_dir(), "warmset.json")


def load_warmset(path=None):
    """The manifest's entry dict ({fingerprint: entry}), tolerating a
    missing, torn, or corrupt file by degrading to empty — prewarm then
    falls back to a cold compile, never a crash."""
    path = path or warmset_path()
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            man = json.load(f)
        ent = man.get("entries", {})
        if not isinstance(ent, dict):
            raise ValueError("entries is not a dict")
        bad = sum(1 for e in ent.values() if not isinstance(e, dict))
        if bad:
            # valid JSON, wrong shape (hand-edited / partially
            # corrupted): drop the damaged entries, keep the rest —
            # never let one bad entry crash a replica's warmup
            ent = {fp: e for fp, e in ent.items()
                   if isinstance(e, dict)}
            _log.warning("warm-set manifest %s has %d non-dict "
                         "entr%s; ignoring them", path, bad,
                         "y" if bad == 1 else "ies")
            tm = _tm()
            if tm._enabled:
                tm.counter("programs/warmset_corrupt_total",
                           "Warm-set manifests found torn/corrupt and "
                           "ignored (cold-compile fallback)").inc()
        return ent
    except (ValueError, OSError) as e:
        _log.warning("warm-set manifest %s is corrupt (%s); "
                     "falling back to cold compile", path, e)
        tm = _tm()
        if tm._enabled:
            tm.counter("programs/warmset_corrupt_total",
                       "Warm-set manifests found torn/corrupt and "
                       "ignored (cold-compile fallback)").inc()
        return {}


def _append_warmset(key):
    """Record one program's fingerprint + abstract input spec in
    ``<cache_dir>/warmset.json`` (atomic_writer: readers never see a
    torn file). Instance-salted keys are NOT recorded: their fingerprints have no cross-process identity,
    so prewarm could never replay them — they would only grow the
    manifest without bound in long-lived processes."""
    if key.instance is not None:
        return
    path = warmset_path()
    from .checkpoint import atomic_writer
    fp = key.fingerprint
    # a fingerprint this process already recorded (or found recorded)
    # skips the locked full-manifest read-modify-write: a hot-swap
    # replacement engine's re-warm would otherwise pay N manifest
    # parses per warmup for entries that are all already on disk
    if (path, fp) in _warmset_seen:
        return
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with _warmset_lock, _warmset_flock(path):
        # (re)load INSIDE both locks: _warmset_lock serializes threads,
        # the flock serializes replicas sharing one cache dir — without
        # it two concurrent warmups would each write back only their
        # own additions and the last rename would drop the other's
        ent = load_warmset(path)
        if fp in ent:
            _warmset_seen.add((path, fp))
            return
        ent[fp] = {"kind": key.kind, "graph": key.graph,
                   "spec": key.spec, "salt": version_salt()}
        try:
            with atomic_writer(path, "w") as f:
                json.dump({"format": WARMSET_FORMAT, "entries": ent},
                          f, indent=1, sort_keys=True)
                f.write("\n")
            _warmset_seen.add((path, fp))
        except OSError as e:
            _log.warning("could not write warm-set manifest %s: %s",
                         path, e)


@contextlib.contextmanager
def _warmset_flock(path):
    """Advisory cross-process lock for the manifest's
    read-modify-write (best effort: platforms without fcntl fall back
    to the in-process lock alone)."""
    try:
        import fcntl
    except ImportError:
        yield
        return
    lock_path = path + ".lock"
    try:
        fd = os.open(lock_path, os.O_CREAT | os.O_RDWR, 0o644)
    except OSError:
        yield
        return
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        os.close(fd)                     # close releases the flock


def note_warm(kind, graph, spec, instance=None):
    """Append a warm-set entry without registering a value — for sites
    whose per-instance objects can't be shared but whose traces should
    replay at the next replica's startup."""
    _append_warmset(ProgramKey(kind, graph, spec, instance))


# ---------------------------------------------------------------------------
# prewarm replay
# ---------------------------------------------------------------------------

def prewarm(sites, include=(), graph=None, manifest=None,
            use_manifest=True):
    """Replay compile traces so every program a replica will serve is
    built (from the persistent cache: loaded off disk) BEFORE traffic
    arrives — the sub-minute-cold-start path /healthz readiness gates
    on.

    ``sites``
        ``{kind: replay_fn}`` — each replay callable takes one spec
        dict and builds/executes that program ON THE CALLING THREAD
        (compile where you execute).
    ``include``
        ``[(kind, spec), ...]`` always replayed (an engine's configured
        ladder) whether or not the manifest mentions them.
    ``graph``
        When given, manifest entries for other graphs are ignored (a
        shared cache dir may hold several models' warm sets).
    ``manifest``
        Explicit warmset.json path (default: the active cache dir's).

    Manifest entries whose version salt mismatches are SKIPPED with a
    warning — replaying a stale trace against a different jax/backend
    would warm the wrong executables and mask real cold compiles. A
    corrupt manifest degrades to the ``include`` set. Replay failures
    of MANIFEST entries are contained per entry (warn + count), so one
    stale spec can't take down startup — but a failure replaying an
    ``include`` entry (the caller's own configured ladder) RAISES:
    reporting a replica warm with a broken ladder would let /healthz
    go ready and push the compile (or its OOM) into the serving path.
    A replay callable may return False to signal it rejected the spec
    (counted skipped, not replayed). Returns a report dict.
    """
    tm = _tm()
    salt = version_salt()
    todo, seen = [], set()
    for kind, spec in include:
        fp = fingerprint(kind, graph or "", spec)
        if fp not in seen:
            seen.add(fp)
            todo.append((kind, spec, True))
    skipped_salt = skipped_site = skipped_graph = 0
    if use_manifest:
        for fp, ent in sorted(load_warmset(manifest).items()):
            kind = ent.get("kind")
            if ent.get("salt") != salt:
                skipped_salt += 1
                continue
            if graph is not None and ent.get("graph") != graph:
                skipped_graph += 1
                continue
            if kind not in sites:
                skipped_site += 1
                continue
            if fp in seen:
                continue
            seen.add(fp)
            todo.append((kind, ent.get("spec") or {}, False))
    if skipped_salt:
        _log.warning(
            "prewarm: skipped %d warm-set entr%s from a different "
            "library/backend version (stale salt; current: %s) — they "
            "will cold-compile on demand instead of replaying wrong "
            "traces", skipped_salt,
            "y" if skipped_salt == 1 else "ies", salt)
        if tm._enabled:
            tm.counter("programs/prewarm_skipped_total",
                       "Warm-set entries skipped at prewarm "
                       "(stale version salt or failed replay)"
                       ).inc(skipped_salt)
    t0 = tm.monotonic()
    c0, d0 = tm.thread_compile_stats()
    replayed = failed = rejected = 0
    for kind, spec, required in todo:
        fn = sites.get(kind)
        if fn is None:
            skipped_site += 1
            continue
        try:
            if fn(spec) is False:        # site rejected the spec
                rejected += 1
            else:
                replayed += 1
        except Exception as e:
            if required:
                # the caller's own configured ladder failed to warm:
                # never report this replica warm over a broken program
                raise
            failed += 1
            _log.warning("prewarm: replay of %s %s failed (%s); "
                         "continuing", kind, spec, e)
            if tm._enabled:
                tm.counter("programs/prewarm_skipped_total",
                           "Warm-set entries skipped at prewarm "
                           "(stale version salt or failed replay)"
                           ).inc()
    c1, d1 = tm.thread_compile_stats()
    report = {"replayed": replayed, "failed": failed,
              "rejected": rejected,
              "skipped_salt": skipped_salt,
              "skipped_graph": skipped_graph,
              "skipped_site": skipped_site,
              "compiles": c1 - c0, "disk_hits": d1 - d0,
              "wall_s": round(tm.monotonic() - t0, 4)}
    if tm._enabled and replayed:
        tm.counter("programs/prewarm_replayed_total",
                   "Warm-set entries replayed at prewarm "
                   "(manifest + configured ladder)").inc(replayed)
    return report


# ---------------------------------------------------------------------------
# donated-loop warmup rule
# ---------------------------------------------------------------------------

def warm_twice(fn, args, rebuild=None, passes=2):
    """Warm a donated compiled loop the way pjit requires, centralized
    so no subsystem rediscovers the rule: pjit keeps ONE executable per
    input-sharding *provenance* (a fresh ``device_put``/``jnp.zeros``
    array keys a different executable than a pjit output does), and
    steady-state traffic only ever presents pjit-output provenance. So:
    warm ON the thread that will execute (the jit cache is per
    thread-local context), run TWO passes — the second against the
    first pass's outputs — and start zero-recompile assertions from
    step 2.

    ``fn(*args)`` is called ``passes`` times. ``rebuild(out, args) ->
    args`` maps one pass's outputs into the next pass's arguments;
    donated buffers MUST come back from the output (a rebuilt fresh
    buffer would re-present the cold provenance and defeat the second
    pass). Returns the final pass's outputs.
    """
    if passes < 1:
        raise MXNetError("warm_twice needs passes >= 1")
    out = fn(*args)
    for _ in range(passes - 1):
        if rebuild is not None:
            args = rebuild(out, args)
        out = fn(*args)
    return out
