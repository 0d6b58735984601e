"""Device contexts.

Re-design of the reference's Context (reference: python/mxnet/context.py):
``mx.cpu()`` / ``mx.gpu(i)`` become ``cpu()`` / ``tpu(i)`` mapping onto JAX
devices. ``gpu`` is kept as an alias for ``tpu`` so reference-style scripts
run unchanged. Contexts are cheap handles. ``tpu(i)`` is the i-th
accelerator of this process and raises when the machine has no such
chip. Only when the JAX platform was explicitly forced to ``cpu``
(``JAX_PLATFORMS=cpu``, the test configuration) does ``tpu(i)`` resolve
to the i-th host device — mirroring how the reference's tests use
multiple ``mx.cpu(i)`` fakes to exercise multi-context code paths
(reference: tests/python/unittest/test_kvstore.py).
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus",
           "num_tpus", "platform_forced_cpu"]

_thread_local = threading.local()


class Context:
    """A device context (reference: python/mxnet/context.py:28)."""

    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5}

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in self.devstr2type:
            raise ValueError("unknown device type %r" % (device_type,))
        # 'gpu' is accepted as an alias so reference scripts keep working
        self.device_typeid = self.devstr2type[device_type]
        self.device_id = int(device_id)

    @property
    def device_type(self) -> str:
        return self.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __str__ = __repr__

    # -- context stack ----------------------------------------------------
    def __enter__(self):
        stack = _ctx_stack()
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _ctx_stack().pop()

    # -- JAX device resolution --------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        ``tpu(i)`` is the i-th accelerator of THIS process (reference
        semantics: mx.gpu(i) is a local device; under multi-host JAX the
        global list spans processes and remote devices are not
        addressable). An id the machine does not have raises, and so
        does a machine with no accelerator — unless the platform was
        explicitly forced to cpu, where ``tpu(i)`` is the i-th host
        device. Host contexts wrap: every ``cpu(i)`` is the same host
        memory."""
        import jax

        if self.device_type != "tpu":
            try:
                devs = jax.local_devices(backend="cpu")
            except RuntimeError:
                # JAX_PLATFORMS names accelerators only: no host backend
                devs = jax.local_devices()
            return devs[self.device_id % len(devs)]
        devs = [d for d in _accel_devices()
                if d.process_index == jax.process_index()]
        if not devs:
            if not platform_forced_cpu():
                raise MXNetError(
                    "%r: JAX found no accelerator (devices: %s) and the "
                    "platform was not forced to cpu. Set JAX_PLATFORMS=cpu "
                    "to run accelerator contexts on host devices."
                    % (self, jax.local_devices()))
            devs = jax.local_devices()
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "%r: this process has %d device(s) of that kind (%s)"
                % (self, len(devs), devs))
        return devs[self.device_id]


def platform_forced_cpu():
    """True when the JAX platform was explicitly forced to cpu
    (``JAX_PLATFORMS=cpu`` or ``jax.config.update("jax_platforms",
    "cpu")``) — the one configuration where accelerator contexts may
    run on host devices."""
    import jax
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def _accel_devices():
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        if "libtpu" in str(e) and "lockfile" in str(e):
            # what the second claimant of a chip gets from libtpu, at
            # once (seen on the v5e, PR 21) — and its advice to delete
            # /tmp/libtpu_lockfile is wrong while the holder lives
            raise MXNetError(
                "the TPU of this host is held by another process. A chip "
                "belongs to ONE process at a time: a parent that touched "
                "JAX holds it, and so does every other worker or replica "
                "started on this host. Run one chip-owning process per "
                "host, or give each process its own chip before it "
                "starts: TPU_VISIBLE_CHIPS=<i> "
                "TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1 "
                "TPU_PROCESS_BOUNDS=1,1,1. (%s)" % e) from e
        raise
    return [d for d in devs if d.platform != "cpu"]


def _ctx_stack():
    if not hasattr(_thread_local, "stack"):
        _thread_local.stack = [Context("cpu", 0)]
    return _thread_local.stack


def current_context() -> Context:
    return _ctx_stack()[-1]


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for :func:`tpu` (compat with reference scripts)."""
    return Context("tpu", device_id)


def num_tpus() -> int:
    return len(_accel_devices())


def num_gpus() -> int:
    return num_tpus()
