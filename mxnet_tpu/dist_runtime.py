"""Multi-host runtime lifecycle for ``dist_tpu_sync``.

One idempotent, refcounted wrapper around the jax distributed runtime
so the kvstore (and anything else that needs the global device view)
can say "make sure the cluster runtime is up" without owning its
lifecycle:

* :func:`acquire` — initialize the runtime exactly once per process
  (explicit ``MXNET_DIST_*`` env first, standard cluster autodetection
  second), or adopt an already-initialized runtime (a launcher that
  called ``jax.distributed.initialize`` itself).
* :func:`release` — drop one reference; when the LAST holder releases
  AND this module performed the initialization, the runtime is torn
  down cleanly.  A runtime initialized by someone else is never shut
  down from here.
* :func:`reinit` — elastic shutdown→reinit cycle: tear the current
  world down (tolerating dead peers) and bring a NEW world up on a
  fresh coordinator, in the same process.  This is the primitive the
  elastic rescale path (elastic.py) is built on.

Why the explicit route builds the coordination client by hand
-------------------------------------------------------------
``jax.distributed.initialize`` wires the XLA coordination service with
defaults that are actively hostile to elastic membership (jax 0.9.0 /
jaxlib 0.9.0 with gloo collectives; ``tests/test_elastic.py`` is the
standing check):

* the client's missed-heartbeat/error-poll handler is a hard
  ``LOG(QFATAL)`` — ``heartbeat_timeout`` (default 100 s) after ANY
  peer dies, every *survivor* is SIGABRTed by its own runtime
  ("Terminating process because the JAX distributed service detected
  fatal errors");
* ``jax.distributed.shutdown()`` runs a shutdown *barrier* that blocks
  until every registered task calls in — with a dead peer it parks
  until the same watchdog kills the process;
* ``State.initialize`` refuses a second call per process, so there is
  no shutdown→reinit cycle at all.

So for the explicit ``MXNET_DIST_COORDINATOR`` route this module
constructs the service/client itself via ``jax._src.lib._jax`` and
installs them into ``jax._src.distributed.global_state`` (the exact
slots jax's own initialize fills, and the place the gloo CPU backend
looks for its KV store):

* ``heartbeat_timeout`` is set effectively infinite — death
  detection belongs to the elastic control plane (collective error /
  stale heartbeat / step watchdog), which reacts in
  ``MXNET_DIST_DEAD_S`` instead of aborting the survivor at 100 s;
* ``shutdown_timeout`` is short, so a shutdown barrier with a dead
  peer resolves in seconds (the agent "proceeds with shutdown anyway",
  which is what stops its heartbeat/error-poll threads);
* ``shutdown_on_destruction=False``, so dropping the last Python
  reference can never run a blocking barrier at an awkward time.

jax 0.9.0's client also takes ``recoverable=``; it stays at its default
(off): with the heartbeat timeout out of the way the kill/rejoin cycles
of ``tests/test_elastic.py`` pass without it, and a rank here never
reconnects to the SAME service — a rescale brings up a new one.

Teardown order matters and is load-bearing: drop the backend first
(the gloo collectives hold a reference to the client's KV store), then
destroy the CLIENT (stops its error-poll thread), and only then the
service — destroying the service while any client still polls turns
the closed socket into the QFATAL this module exists to avoid.

Configuration (config.py):

* ``MXNET_DIST_COORDINATOR`` — ``host:port`` of process 0's
  coordinator service.  Setting it (plus the two below) is the
  explicit, works-anywhere route — the CPU/gloo acceptance tests
  (tests/test_dist_tpu_sync.py) use it, and it is the only route that
  supports :func:`reinit` (elastic rescale).
* ``MXNET_DIST_NUM_PROCESSES`` / ``MXNET_DIST_PROCESS_ID`` — world
  size and this process's rank.

Without ``MXNET_DIST_*``, :func:`env_configured` falls back to the
standard signals ``jax.distributed.initialize()`` autodetects itself
(Cloud TPU metadata, SLURM, Open MPI) so a TPU pod slice launched
through the normal tooling needs no extra variables.

On a CPU backend the gloo collectives implementation is selected
before initialization (the raw CPU backend cannot run multiprocess
computations).
"""
from __future__ import annotations

import gc
import logging
import os
import threading

from .base import MXNetError

__all__ = ["acquire", "release", "initialize", "shutdown", "teardown",
           "reinit", "is_initialized", "env_configured", "process_count",
           "process_index", "generation"]

_log = logging.getLogger(__name__)

_lock = threading.Lock()
_refs = [0]          # live acquire() holders
_owned = [False]     # did THIS module initialize the runtime?
_manual = [False]    # did we build the client/service by hand?
_generation = [0]    # completed initialize cycles (elastic member epochs)

# Coordination-service tuning for the hand-built route.  The heartbeat
# timeout is effectively infinite (30 days): membership death detection
# is the elastic layer's job, not the coordination service's QFATAL.
_HB_TIMEOUT_S = 30 * 24 * 3600
_INIT_TIMEOUT_S = 60
_SHUTDOWN_TIMEOUT_S = 2

# standard env signals jax.distributed.initialize() can autodetect a
# cluster from without explicit arguments
_AUTO_ENV = ("SLURM_JOB_ID", "OMPI_COMM_WORLD_SIZE",
             "TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS",
             "COORDINATOR_ADDRESS")


def _cfg(name):
    from .config import get
    return get(name)


def _global_state():
    from jax._src import distributed as _d
    return _d.global_state


def is_initialized():
    """Whether this process already has a live distributed runtime
    (ours or anyone's)."""
    try:
        return _global_state().client is not None
    except Exception:
        return False


def env_configured():
    """Whether the environment describes a multi-process cluster this
    process could join: explicit ``MXNET_DIST_*`` settings, or one of
    the standard signals jax autodetects."""
    if _cfg("MXNET_DIST_COORDINATOR"):
        return True
    return any(os.environ.get(v) for v in _AUTO_ENV)


def _select_cpu_collectives():
    """Route multiprocess CPU computations over gloo (the raw CPU
    backend cannot run multiprocess programs); a no-op unless the
    platform was forced to cpu."""
    import jax
    from .context import platform_forced_cpu
    if platform_forced_cpu():
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _manual_initialize(coord, num_processes, process_id):
    """Build the coordination service (rank 0) + client by hand and
    install them into jax's global state — the elastic-safe equivalent
    of ``jax.distributed.initialize`` (see module docstring)."""
    from jax._src.lib import _jax as xe
    st = _global_state()
    service = None
    if process_id == 0:
        bind = "[::]:" + coord.rsplit(":", 1)[1]
        service = xe.get_distributed_runtime_service(
            bind, num_processes,
            heartbeat_timeout=_HB_TIMEOUT_S,
            shutdown_timeout=_SHUTDOWN_TIMEOUT_S)
    try:
        client = xe.get_distributed_runtime_client(
            coord, process_id,
            init_timeout=_INIT_TIMEOUT_S,
            shutdown_timeout=_SHUTDOWN_TIMEOUT_S,
            heartbeat_timeout=_HB_TIMEOUT_S,
            shutdown_on_destruction=False,
            use_compression=True)
        client.connect()
    except Exception:
        if service is not None:
            del service
            gc.collect()
        raise
    st.service = service
    st.client = client
    st.process_id = process_id
    st.num_processes = num_processes
    st.coordinator_address = coord


def _initialize_locked(coordinator=None, num_processes=None,
                       process_id=None):
    import jax
    if is_initialized():
        return False
    coord = coordinator or _cfg("MXNET_DIST_COORDINATOR")
    if num_processes is None and coord:
        num_processes = int(_cfg("MXNET_DIST_NUM_PROCESSES"))
    if process_id is None and coord:
        process_id = int(_cfg("MXNET_DIST_PROCESS_ID"))
    try:
        if coord:
            _select_cpu_collectives()
            _manual_initialize(coord, int(num_processes), int(process_id))
            # keep env/config coherent for everything that re-reads the
            # world description (kvstore sizing, respawned children)
            os.environ["MXNET_DIST_COORDINATOR"] = coord
            os.environ["MXNET_DIST_NUM_PROCESSES"] = str(int(num_processes))
            os.environ["MXNET_DIST_PROCESS_ID"] = str(int(process_id))
            _owned[0] = True
            _manual[0] = True
            _generation[0] += 1
            return True
        if any(os.environ.get(v) for v in _AUTO_ENV):
            _select_cpu_collectives()
            jax.distributed.initialize()   # standard autodetection
            _owned[0] = True
            _manual[0] = False
            _generation[0] += 1
            return True
    except MXNetError:
        raise
    except Exception as e:
        raise MXNetError(
            "distributed runtime initialization failed for the "
            "configured cluster (%s): %s" % (coord or "autodetected env", e))
    return False


def initialize(coordinator=None, num_processes=None, process_id=None):
    """Idempotent distributed-runtime bring-up.

    Returns True when THIS call initialized the runtime, False when it
    was already up or no cluster is configured.  Raises
    :class:`MXNetError` when the environment names a cluster but the
    join fails — silently training single-process after a botched
    rendezvous would corrupt the run, not degrade it."""
    with _lock:
        return _initialize_locked(coordinator, num_processes, process_id)


def _teardown_locked(graceful=True):
    """Tear down the runtime IF this module initialized it (no-op
    otherwise — never shut down a launcher-owned runtime).  Caller
    holds ``_lock``.

    Safe with dead peers: the shutdown barrier resolves within
    ``_SHUTDOWN_TIMEOUT_S`` and failure is tolerated (the coordination
    agent stops its threads either way).  The client is destroyed
    BEFORE the service — the reverse order turns the service's closed
    socket into a fatal error on the client's poll thread."""
    if not _owned[0]:
        return
    _owned[0] = False
    if not _manual[0]:
        try:
            import jax
            jax.distributed.shutdown()
        except Exception as e:       # already down / interpreter exit
            _log.debug("jax.distributed.shutdown: %s", e)
        return
    try:
        import jax
        import jax.extend.backend as _jeb
        st = _global_state()
        if st.client is not None:
            try:
                st.client.shutdown()
            except Exception as e:
                # expected with dead peers: the barrier fails after
                # _SHUTDOWN_TIMEOUT_S and the agent shuts down anyway
                _log.info("distributed client shutdown (dead peers "
                          "tolerated): %s", str(e)[:200])
        jax.clear_caches()
        _jeb.clear_backends()
        st.client = None
        st.preemption_sync_manager = None
        gc.collect()                 # stop client heartbeat/poll threads
        st.service = None
        gc.collect()                 # only now close the service socket
        st.process_id = 0
        st.num_processes = 1
        st.coordinator_address = None
    except Exception as e:
        _log.warning("distributed runtime teardown: %s", e)


def shutdown():
    with _lock:
        _teardown_locked()


def teardown(graceful=True):
    """Tear the runtime down NOW (elastic path; refcount survives so
    the holders' eventual release() calls stay balanced)."""
    with _lock:
        _teardown_locked(graceful)


def reinit(coordinator, num_processes, process_id):
    """Elastic shutdown→reinit cycle: tear down the current world
    (tolerating dead peers) and join a NEW world in-place.

    Invalidates the process-wide program-registry version salt — the
    salt embeds ``processes=N``, so programs built for the new world
    re-fingerprint (and replay from the persistent compile cache as
    disk hits rather than recompiles)."""
    with _lock:
        _teardown_locked(graceful=False)
        ok = _initialize_locked(coordinator, num_processes, process_id)
        if not ok:
            raise MXNetError("elastic reinit failed to join the new "
                             "world at %s" % coordinator)
    try:
        from . import programs
        programs.invalidate_version_salt()
    except Exception:
        pass
    return True


def acquire():
    """Refcounted ensure-initialized; pair with :func:`release`.

    Initialization is attempted whenever no runtime is live — NOT only
    on the first reference: an early holder acquired before the cluster
    env was set (e.g. ``io.dist_parts`` on a laptop) must not suppress
    a later holder's rendezvous."""
    with _lock:
        if not is_initialized():
            _initialize_locked()   # marks _owned when it performs the init
        _refs[0] += 1


def release():
    """Drop one :func:`acquire` reference; the last release shuts the
    runtime down when this module owns it."""
    with _lock:
        if _refs[0] > 0:
            _refs[0] -= 1
            if _refs[0] == 0:
                _teardown_locked()


def generation():
    """Completed initialize cycles in this process (1 after the first
    bring-up; bumps on every elastic :func:`reinit`)."""
    return _generation[0]


def process_count():
    try:
        import jax
        return int(jax.process_count())
    except Exception:
        return 1


def process_index():
    try:
        import jax
        return int(jax.process_index())
    except Exception:
        return 0
