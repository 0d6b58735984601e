"""Device mesh management.

Reference analog: the context lists passed to Module/-Trainer
(`ctx=[mx.gpu(0), mx.gpu(1), ...]`, executor_group.py:143) and the KVStore
device topology (comm_tree.h link solver). On TPU the mesh IS the
topology: axes map onto ICI rings, so laying out ('dp','tp') over a pod
slice makes gradient reduction ride ICI without any tree solver.
"""
from __future__ import annotations

import threading

__all__ = ["make_mesh", "current_mesh", "set_mesh", "data_parallel_sharding",
           "replicated_sharding", "global_dp_mesh", "mesh_process_count",
           "host_local_value", "make_replicated_global",
           "make_batch_global", "make_accum_batch_global"]

_state = threading.local()


def make_mesh(shape=None, axis_names=("dp",), devices=None):
    """Create a Mesh over the visible devices.

    ``shape``: tuple of axis sizes (product must divide the device count),
    or None to put every device on the first axis."""
    import jax
    import numpy as np
    devs = devices if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devs),)
    shape = tuple(int(s) for s in shape)
    n = int(np.prod(shape))
    if n > len(devs):
        raise ValueError("mesh shape %s needs %d devices, have %d"
                         % (shape, n, len(devs)))
    arr = np.asarray(devs[:n]).reshape(shape)
    from jax.sharding import Mesh
    return Mesh(arr, axis_names[:len(shape)])


def set_mesh(mesh):
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    return prev


def current_mesh():
    return getattr(_state, "mesh", None)


def data_parallel_sharding(mesh, axis="dp", ndim=2):
    """NamedSharding splitting the leading (batch) dim over ``axis``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# multi-host (dist_tpu_sync) mesh + placement helpers
# ---------------------------------------------------------------------------

def global_dp_mesh(axis="dp"):
    """1-D data-parallel mesh over EVERY device of EVERY process, in
    canonical ``(process_index, device id)`` order — each process's
    local devices own a contiguous run of mesh positions, so rank r's
    local batch maps onto global batch rows ``[r*local, (r+1)*local)``.
    This is the mesh ``dist_tpu_sync`` folds the gradient all-reduce
    into (GSPMD inserts the ``psum`` over the 'dp' axis inside the
    fused train-step program)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    return Mesh(np.array(devs), (axis,))


def mesh_process_count(mesh):
    """How many processes own devices of ``mesh`` (1 = fully local)."""
    return len({d.process_index for d in mesh.devices.flat})


def host_local_value(arr):
    """This process's addressable view of a (possibly multi-process)
    jax array: the full value for a replicated array, the local rows
    (concatenated over local shards, mesh order) for a batch array
    sharded on dim 0.  Fully-addressable arrays pass through — the
    single-process path pays nothing."""
    import jax
    if not isinstance(arr, jax.Array) or arr.is_fully_addressable:
        return arr
    shards = {}
    for s in arr.addressable_shards:
        key = tuple(sl.start or 0 for sl in s.index)
        shards.setdefault(key, s.data)
    if len(shards) == 1:                   # replicated: any shard is all
        return next(iter(shards.values()))
    # multiple local shards (several local devices): assemble on host —
    # the shards are committed to DIFFERENT devices, and jax refuses a
    # device computation over mixed placements
    import numpy as np
    return np.concatenate(
        [np.asarray(d) for _, d in sorted(shards.items())], axis=0)


def make_replicated_global(mesh, host_value):
    """Global replicated array over a multi-process ``mesh`` from a
    host value every process holds identically (params, optimizer
    state): the value lands on each LOCAL device and the shards
    assemble into one global array — no cross-host transfer, because
    replication needs none when every host already has the value."""
    import jax
    import numpy as np
    data = np.asarray(host_value)
    sh = replicated_sharding(mesh)
    arrs = [jax.device_put(data, d) for d in mesh.local_devices]
    return jax.make_array_from_single_device_arrays(data.shape, sh, arrs)


def make_batch_global(mesh, host_local_batch, axis="dp"):
    """Global batch array sharded on dim 0 over ``axis``, assembled
    from each process's LOCAL batch rows (the per-host input-sharding
    contract: rank r feeds shard r of the iterator, see
    ``io.dist_parts``).  Global batch = local batch x process count;
    every process must contribute the same local batch size."""
    import jax
    import numpy as np
    data = np.asarray(host_local_batch)
    sh = data_parallel_sharding(mesh, axis=axis, ndim=max(data.ndim, 1))
    return jax.make_array_from_process_local_data(sh, data)


def make_accum_batch_global(mesh, host_local_batch, axis="dp"):
    """Microbatched global batch for the gradient-accumulation fused
    step: local rows ``[A, L, ...]`` (A microbatches of L rows each)
    assemble into a global ``[A, world*L, ...]`` sharded on dim **1**
    (``P(None, 'dp')``) — microbatch ``a``'s global rows are the
    concatenation of every process's ``a``-th microbatch, exactly the
    rows the pre-rescale world's ranks ``a*world..(a+1)*world-1`` fed
    in one step (see ``elastic.plan_microbatches``)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    data = np.asarray(host_local_batch)
    if data.ndim < 2:
        raise ValueError("accum batch needs shape [A, L, ...], got %s"
                         % (tuple(data.shape),))
    sh = NamedSharding(mesh, P(None, axis, *([None] * (data.ndim - 2))))
    return jax.make_array_from_process_local_data(sh, data)
