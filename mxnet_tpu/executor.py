"""Symbolic executor.

Reference: python/mxnet/executor.py + src/executor/graph_executor.cc.

TPU-native design: binding compiles the whole symbol graph into ONE jitted
XLA program per (is_train, shape-signature) — the analog of
GraphExecutor::Init's pass pipeline (InitGraph → InferShape → PlanMemory →
InitCachedOps, graph_executor.cc:297-673), with XLA doing memory planning
and op bulking. ``backward`` jits the vjp of the same pure graph function,
rematerializing the forward (FLOPs-for-HBM, the right TPU default).
``train_step`` goes one step further: forward, every gradient, the
optimizer update, and the aux-state update in ONE donated XLA program —
the whole training step is a single Python→XLA dispatch (the analog of
the reference's engine op bulking plus src/operator/optimizer_op.cc's
fused update kernels, collapsed across the step boundary).
"""
from __future__ import annotations

import numpy as _np

from .base import MXNetError, install_donation_warning_filter
from .ndarray.ndarray import NDArray, zeros
from .context import current_context
from . import health as _health
from . import programs as _pg
from . import random as _random
from . import telemetry as _tm
from . import tracing as _tr
from .ops import registry as _reg
from .symbol.symbol import _graph_eval_fn, _topo

__all__ = ["Executor"]


def _note_graph_compile():
    """Count a whole-graph jit build (forward or vjp specialization)."""
    if _tm._enabled:
        _tm._ensure_compile_listener()
        _tm.counter("executor/graph_compile_total",
                    "Executor whole-graph jit builds "
                    "(forward + vjp specializations)").inc()


class Executor(object):
    """Bound computation graph (reference: executor.py Executor)."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None):
        self._symbol = symbol
        self._ctx = ctx or current_context()
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()

        if isinstance(args, dict):
            missing = [n for n in arg_names if n not in args]
            if missing:
                raise MXNetError("bind missing arguments: %s" % missing)
            self.arg_arrays = [args[n] for n in arg_names]
        else:
            if len(args) != len(arg_names):
                raise MXNetError("bind expects %d args, got %d"
                                 % (len(arg_names), len(args)))
            self.arg_arrays = list(args)
        self.arg_dict = dict(zip(arg_names, self.arg_arrays))

        if aux_states is None:
            aux_states = []
        if isinstance(aux_states, dict):
            self.aux_arrays = [aux_states[n] for n in aux_names]
        else:
            self.aux_arrays = list(aux_states)
        if len(self.aux_arrays) != len(aux_names):
            raise MXNetError("bind expects %d aux states, got %d"
                             % (len(aux_names), len(self.aux_arrays)))
        self.aux_dict = dict(zip(aux_names, self.aux_arrays))

        # grad_req: str | list | dict
        if isinstance(grad_req, str):
            reqs = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            reqs = dict(zip(arg_names, grad_req))
        else:
            reqs = {n: grad_req.get(n, "null") for n in arg_names}
        self._grad_req = reqs

        if args_grad is None:
            self.grad_arrays = [
                zeros(a.shape, ctx=self._ctx, dtype=a.dtype)
                if reqs[n] != "null" else None
                for n, a in zip(arg_names, self.arg_arrays)]
        elif isinstance(args_grad, dict):
            self.grad_arrays = [args_grad.get(n) for n in arg_names]
        else:
            self.grad_arrays = list(args_grad)
        self.grad_dict = dict(zip(arg_names, self.grad_arrays))

        self._arg_names = arg_names
        self._aux_names = aux_names
        self._needs_rng = any(
            (not n.is_var) and _reg.get_op(n.op).needs_rng
            for n in _topo(symbol._entries))
        # graph fingerprint for the process-wide program registry
        # (programs.py): executors bound to the same symbol at the same
        # shapes SHARE one jitted program — a hot-swap replacement
        # engine re-warms its ladder as cache hits, and a fresh process
        # loads it from the persistent compile cache on disk
        self._graph_hash = _pg.graph_hash(symbol)
        self._jitted = {}               # memo over the registry (keys
        self._vjp_jitted = {}           # re-fingerprint per entry; the
        self._fused_jitted = {}         # registry owns the programs)
        self._fwd_keys = {}             # is_train -> ProgramKey
        self._rule_salts = {}           # closure rule -> instance salt
        # health-layer accounting: captured cost-analysis records per
        # program, grad-norm EMA for spike detection, and the previous
        # step-end stamp the throughput-MFU interval is measured from
        self._fwd_cost = {}
        self._fused_costs = {}
        self._fused_cost_rec = None
        self._numerics_state = {}
        self._pending_sentinel = None
        self._last_step_end = None
        self.outputs = []
        self._monitor_callback = None
        self._dp_mesh = None
        self._dp_batch_names = ()
        self._dp_nproc = 1
        self._allreduce_bytes = 0
        # the look-aside of ``prestage``: name -> (source buffer, placed
        # array) for the NEXT step's inputs; consumed once, by identity
        self._prestaged = {}
        if _tm._enabled:
            _tm.counter("executor/bind_total",
                        "Executor binds (graph → buffers)").inc()
        from . import profiler as _prof
        _prof.record_instant("executor_bind", "executor",
                             {"args": len(arg_names), "aux": len(aux_names)})

    # -- data parallelism --------------------------------------------------
    def set_dp_mesh(self, mesh, batch_arg_names):
        """Make this executor data-parallel over ``mesh`` (1-D, axis 'dp').

        The TPU-native DataParallelExecutorGroup (reference:
        python/mxnet/module/executor_group.py:143,310-341): instead of one
        executor per device plus a KVStore reduce, the SAME compiled
        program runs over the mesh with batch args sharded on dim 0 and
        parameters replicated; GSPMD partitions the compute and inserts
        the gradient all-reduce that `Comm`/NCCL performed in the
        reference. ``batch_arg_names`` lists the args sharded on dim 0
        (data + labels).

        A mesh spanning MULTIPLE PROCESSES (``dist_tpu_sync``:
        parallel.mesh.global_dp_mesh) makes this the pod-scale path:
        each process stages its LOCAL batch shard into a global array
        (per-host input sharding), params ride replicated, and the
        gradient ``psum`` crosses hosts on ICI/DCN inside the same
        donated program — zero per-step host round-trips."""
        from .parallel.mesh import mesh_process_count
        self._dp_mesh = mesh
        self._dp_batch_names = tuple(batch_arg_names)
        self._dp_nproc = mesh_process_count(mesh)
        self._prestaged = {}            # placed for the layout before
        # the mesh signature is part of every program fingerprint:
        # drop the memos so programs built before the mesh was set
        # can't be confused with their sharded successors (rebuilds
        # are registry hits when an equivalent program already exists)
        self._jitted.clear()
        self._vjp_jitted.clear()
        self._fused_jitted.clear()
        self._fwd_keys.clear()
        # re-place already-bound buffers so the first forward starts from
        # consistently-committed arrays
        for n, arr in list(self.arg_dict.items()):
            if arr is not None:
                arr._set_data(self._dp_place(n, arr._data))
        for n, arr in self.aux_dict.items():
            arr._set_data(self._dp_place(n, arr._data))
        for n, arr in self.grad_dict.items():
            if arr is not None:
                arr._set_data(self._dp_place(n, arr._data))

    def _dp_sharding(self, name, data):
        """The mesh sharding ``data`` is declared to have as argument
        ``name``: batch args split on dim 0 over 'dp', all else
        replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = self._dp_mesh
        if name not in self._dp_batch_names:
            return NamedSharding(mesh, P())
        ndev = mesh.shape["dp"]
        local_div = (len(mesh.local_devices) if self._dp_nproc > 1
                     else ndev)
        if data.ndim == 0 or data.shape[0] % local_div != 0:
            raise MXNetError(
                "data-parallel Module: batch dim of %r (shape %s) must "
                "be divisible by the %d devices"
                % (name, tuple(data.shape), local_div))
        return NamedSharding(mesh, P("dp", *([None] * (data.ndim - 1))))

    def _dp_place(self, name, data):
        """device_put ``data`` to its declared mesh sharding if it is not
        already there (no-op on the steady-state path).

        On a multi-process mesh the staged value is this process's
        LOCAL contribution: batch args assemble into a global array
        whose rows are each host's shard (global batch = local batch x
        process count), replicated args land on the local devices only
        (every host already holds the value — replication moves no
        bytes)."""
        import jax
        sh = self._dp_sharding(name, data)
        if getattr(data, "sharding", None) == sh:
            return data
        if self._dp_nproc == 1:
            return jax.device_put(data, sh)
        from .parallel.mesh import (host_local_value, make_batch_global,
                                    make_replicated_global)
        local = host_local_value(data)      # host/local view to restage
        if name in self._dp_batch_names:
            return make_batch_global(self._dp_mesh, local)
        return make_replicated_global(self._dp_mesh, local)

    def _place_accum(self, name, value):
        """Place one microbatched train-step input (host-local
        ``[A, L, ...]``): sharded ``P(None, 'dp')`` on a mesh (global
        ``[A, world*L, ...]`` — dim 1 is the batch), plain device array
        off-mesh (the shrunk-to-one elastic survivor)."""
        import jax
        data = _np.asarray(value, dtype=self.arg_dict[name].dtype) \
            if not isinstance(value, jax.Array) else value
        mesh = self._dp_mesh
        if mesh is None:
            return jax.device_put(data, self._ctx.jax_device())
        from jax.sharding import NamedSharding, PartitionSpec as P
        if self._dp_nproc == 1:
            spec = P(None, "dp", *([None] * (getattr(data, "ndim", 2) - 2)))
            return jax.device_put(data, NamedSharding(mesh, spec))
        from .parallel.mesh import make_accum_batch_global
        return make_accum_batch_global(mesh, data)

    # -- compilation -------------------------------------------------------
    def _buffer_sig(self):
        """Abstract input spec of the bound buffers ([(name, shape,
        dtype)] over args + aux) — the shape component of every program
        fingerprint, so the same graph bound at two shapes registers
        two distinct entries."""
        sig = [[n, list(a.shape), str(a.dtype)]
               for n, a in zip(self._arg_names, self.arg_arrays)]
        sig += [[n, list(a.shape), str(a.dtype)]
                for n, a in zip(self._aux_names, self.aux_arrays)]
        return sig

    def _mesh_sig(self):
        """Sharding/mesh fingerprint component (None off-mesh)."""
        if self._dp_mesh is None:
            return None
        return {"axes": {k: int(v) for k, v in self._dp_mesh.shape.items()},
                "batch": sorted(self._dp_batch_names)}

    def _fwd(self, is_train):
        is_train = bool(is_train)
        j = self._jitted.get(is_train)
        if j is None:
            key = _pg.ProgramKey(
                "executor_forward", self._graph_hash,
                {"is_train": is_train, "args": self._buffer_sig(),
                 "mesh": self._mesh_sig(), "rng": self._needs_rng})

            def build():
                import jax
                fn = _graph_eval_fn(self._symbol, is_train)
                _note_graph_compile()
                return jax.jit(fn)

            j = _pg.get_or_build(key, build)
            self._jitted[is_train] = j
            self._fwd_keys[is_train] = key
        return j

    def _vjp(self, grad_names_key, add_names_key=()):
        """Jitted (arg_env, fixed_env, key, cotangents, accumulators) ->
        grads for the arguments listed in ``grad_names_key``. Arguments in
        ``add_names_key`` (grad_req='add') have their existing gradient
        buffers summed INSIDE the program — no per-parameter host
        dispatch after it returns."""
        cache_key = (grad_names_key, add_names_key)
        j = self._vjp_jitted.get(cache_key)
        if j is None:
            key = _pg.ProgramKey(
                "executor_vjp", self._graph_hash,
                {"grads": list(grad_names_key),
                 "adds": list(add_names_key),
                 "args": self._buffer_sig(), "mesh": self._mesh_sig(),
                 "rng": self._needs_rng})

            def build():
                import jax
                fn = _graph_eval_fn(self._symbol, True)

                def run(genv, fenv, key, cts, acc):
                    def fwd(ge):
                        env = dict(fenv)
                        env.update(ge)
                        outs, _aux = fn(env, key)
                        return outs

                    _outs, vjp = jax.vjp(fwd, genv)
                    (gs,) = vjp(tuple(cts))
                    gs = dict(gs)
                    for n in add_names_key:
                        gs[n] = acc[n] + gs[n]
                    return gs

                _note_graph_compile()
                return jax.jit(run)

            j = _pg.get_or_build(key, build)
            self._vjp_jitted[cache_key] = j
        return j

    # -- execution ---------------------------------------------------------
    def _env(self):
        env = {n: a._data for n, a in zip(self._arg_names, self.arg_arrays)}
        env.update({n: a._data
                    for n, a in zip(self._aux_names, self.aux_arrays)})
        if self._dp_mesh is not None:
            # keep every input committed to its mesh sharding; steady-state
            # this is a cheap sharding-equality check per array
            for n in env:
                placed = self._dp_place(n, env[n])
                if placed is not env[n]:
                    env[n] = placed
                    tgt = (self.arg_dict[n] if n in self.arg_dict
                           else self.aux_dict.get(n))
                    if tgt is not None:
                        tgt._set_data(placed)
        return env

    def _place_input(self, name, value):
        """One forward/train_step input as the array the programs take:
        committed to this executor's device (and dp-mesh sharding). Host
        arrays go through jax.device_put to self._ctx — jnp.asarray
        would land them on JAX's default device and ignore the bound
        context."""
        import jax
        if isinstance(value, NDArray):
            # an iterator's batch lives where its context put it — the
            # host, by default: this is the ONE H2D copy of the step (a
            # no-op for a batch already on the device)
            data = value._data
        elif isinstance(value, jax.Array):
            # already on device: cast/move device-side, never via host
            data = value
            want = self.arg_dict[name].dtype
            if data.dtype != want:
                data = data.astype(want)
        else:
            data = _np.asarray(value, dtype=self.arg_dict[name].dtype)
        if self._dp_mesh is not None:
            return self._dp_place(name, data)
        return jax.device_put(data, self._ctx.jax_device())

    @staticmethod
    def _source(value):
        """The buffer an input is made from: an NDArray's immutable
        ``jax.Array`` (every write to the NDArray replaces it), else the
        value itself."""
        return value._data if isinstance(value, NDArray) else value

    def _take_prestaged(self, name, source):
        """The array ``prestage`` placed for ``name``, if it was made
        from the very buffer now offered and lies where this executor
        would place it now; else None. The entry goes either way."""
        held = self._prestaged.pop(name, None)
        if held is None or held[0] is not source:
            return None
        placed = held[1]
        if self._dp_mesh is not None:
            if placed.sharding != self._dp_sharding(name, placed):
                return None
        elif placed.devices() != {self._ctx.jax_device()}:
            return None
        return placed

    def _stage_input(self, name, value):
        """Bind one forward/train_step input (``_place_input``). The
        look-aside of ``prestage`` is asked first: an input whose buffer
        IS the one a ``prestage`` call placed ahead binds that array (a
        hit: its H2D copy was issued a step ago) and every other one is
        placed now (a miss: the path of a call nobody prepared). The
        look-aside's entry for ``name`` is dropped in both cases, so a
        placed array is bound to one step at most. Returns whether it
        was a hit."""
        if name not in self.arg_dict:
            raise MXNetError("unknown forward argument %r" % name)
        data = self._take_prestaged(name, self._source(value))
        hit = data is not None
        if not hit:
            data = self._place_input(name, value)
        if _tm._enabled:
            if hit:
                _tm.counter("executor/prestage_hits_total",
                            "Step inputs bound from the array "
                            "Executor.prestage placed ahead").inc()
            else:
                _tm.counter("executor/prestage_misses_total",
                            "Step inputs placed at the call itself "
                            "(never prepared, or not the prepared "
                            "buffer)").inc()
        self.arg_dict[name]._set_data(data)
        return hit

    def _stage_inputs(self, feed):
        """Bind a call's inputs under one span: the host's side of the
        step's H2D copy (``device_put`` returns before the copy lands).
        ``prestaged`` on the span: every input came from the
        look-aside, which is empty after ANY call: what ``prestage``
        placed is for the call right after it, or for none."""
        if feed:
            with _tr.child_span("executor.stage_input") as span:
                hits = [self._stage_input(k, v) for k, v in feed.items()]
                span.set_attr("prestaged", all(hits))
        self._prestaged = {}

    def prestage(self, feed):
        """Place the NEXT call's inputs now, while the device still runs
        this one: ``feed`` as ``forward(**kwargs)`` / ``train_step(feed=)``
        take it, each input placed exactly as ``_stage_input`` would
        (same device or mesh sharding, same dtype handling, the same
        ``executor.stage_input`` span) and kept in a one-call look-aside
        ``{name: (source, placed)}``. Nothing bound changes: not
        ``arg_dict``, not ``outputs``, no program runs.

        ``source`` is the immutable ``jax.Array`` the input was made
        from (an NDArray's ``_data``: writing to the NDArray replaces
        it, so a batch written to after this call no longer matches).
        An input that is neither (a numpy array can be written in
        place, so its identity proves nothing) is left to the call.
        A second ``prestage`` replaces the first."""
        import jax
        self._prestaged = {}
        if not feed:
            return
        held = {}
        with _tr.child_span("executor.stage_input", attrs={"ahead": True}):
            for name, value in feed.items():
                source = self._source(value)
                if name in self.arg_dict and isinstance(source, jax.Array):
                    held[name] = (source, self._place_input(name, value))
        self._prestaged = held

    def drop_prestaged(self):
        """Empty the look-aside (the module's shapes changed)."""
        self._prestaged = {}

    def forward(self, is_train=False, **kwargs):
        """Run the compiled forward program
        (reference: GraphExecutor::RunOps, graph_executor.cc:64,1318)."""
        self._stage_inputs(kwargs)
        key = _random.next_key() if self._needs_rng else None
        fwd = self._fwd(bool(is_train))
        env = self._env()
        with _tr.child_span("executor.forward",
                            attrs={"is_train": bool(is_train)}):
            outs, new_aux = fwd(env, key)
        if bool(is_train) not in self._fwd_cost:
            # one-shot roofline capture per forward program (an HLO
            # cost pass over the lowered module, not a second compile);
            # keyed by a process-unique sequence, never id(self) — a
            # GC-reused address must not inherit a dead graph's FLOPs
            pkey = self._fwd_keys.get(bool(is_train))
            self._fwd_cost[bool(is_train)] = _health.capture_cost(
                "executor_forward", _health.next_cost_key("fwd"),
                fwd, (env, key), pkey=pkey)
            if pkey is not None:
                _pg.attach_cost(pkey, self._fwd_cost[bool(is_train)])
        self._last_key = key
        for name, val in new_aux.items():
            self.aux_dict[name]._set_data(val)
        # multi-process mesh: outputs stay GLOBAL jax arrays (zero
        # per-step host traffic); NDArray.asnumpy takes this process's
        # addressable view lazily at the first host read
        self.outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        if self._monitor_callback is not None:
            for name, arr in zip(self._symbol.list_outputs(), self.outputs):
                self._monitor_callback(name, arr)
        return self.outputs

    @staticmethod
    def _normalize_out_grads(out_grads):
        """Output cotangents -> tuple of raw jax arrays (shared by
        backward() and train_step() so their semantics cannot drift)."""
        import jax.numpy as jnp
        if isinstance(out_grads, NDArray):
            out_grads = [out_grads]
        return tuple(g._data if isinstance(g, NDArray) else jnp.asarray(g)
                     for g in out_grads)

    def backward(self, out_grads=None, is_train=True):
        """Gradients of outputs w.r.t. bound args, accumulated per
        grad_req (reference: GraphExecutor backward range run)."""
        import jax.numpy as jnp
        outs = self.outputs
        if not outs:
            raise MXNetError("call forward(is_train=True) before backward")
        if out_grads is None:
            cts = [jnp.ones(o.shape, dtype=o.dtype) for o in outs]
        else:
            cts = list(self._normalize_out_grads(out_grads))
        grad_names = tuple(n for n in self._arg_names
                           if self._grad_req[n] != "null")
        if not grad_names:
            return
        add_names = tuple(n for n in grad_names
                          if self._grad_req[n] == "add"
                          and self.grad_dict[n] is not None)
        env = self._env()
        genv = {n: env.pop(n) for n in grad_names}
        key = getattr(self, "_last_key", None)
        if self._needs_rng and key is None:
            key = _random.next_key()
        acc = {n: self.grad_dict[n]._data for n in add_names}
        gs = self._vjp(grad_names, add_names)(genv, env, key,
                                              tuple(cts), acc)
        for n in grad_names:
            tgt = self.grad_dict[n]
            if tgt is None:
                continue
            tgt._set_data(gs[n])

    # -- fused train step --------------------------------------------------
    def _build_fused_step(self, rule, update_names, default_ct, donate,
                          hyper_keys, numerics="off", accum=1,
                          accum_names=()):
        """Trace + jit ONE program computing forward outputs, all
        gradients (jax.vjp over the same pure graph function), the
        optimizer update for every parameter in ``update_names`` via
        ``rule``, and the aux-state updates. Parameter and optimizer-state
        buffers are donated so XLA aliases them input→output: an in-place
        HBM update with no per-parameter copies. The hyper-parameters
        arrive as ONE float32 array (a row per name in ``update_names``,
        a column per key in ``hyper_keys``); each rule call reads its
        row as a dict of scalars in its weight's dtype. The rule is
        elementwise, so XLA fuses it over each parameter in the layout it
        has; under the dp mesh every operand of it is replicated (GSPMD
        all-reduces the gradient on its way in), so each device runs it
        on its own replica as it stands.

        ``numerics`` != 'off' folds the health sentinels into the SAME
        program: a loss proxy (mean of the first output), the global
        gradient L2 norm, and the nonfinite-element count — all over
        the gradients the program already holds, so the sentinel costs
        a handful of reductions and ZERO extra host dispatches or
        recompiles (the hyper array stays a traced argument). ``full``
        additionally returns per-parameter norm/nonfinite vectors for
        blast-radius attribution. Everything is packed into ONE flat
        float32 vector so the host pays a single small D2H fetch per
        step."""
        import jax
        import jax.numpy as jnp
        from .optimizer import unpack_fused_hyper
        fn = _graph_eval_fn(self._symbol, True)

        def _sentinel(gs, outs):
            # step mode costs ONE reduction pass over each gradient:
            # the per-param squared-sum. Nonfinite detection falls out
            # free — squares are non-negative, so a single NaN/inf
            # element makes the param's squared-sum NaN/inf (nothing
            # can cancel it) and the "nonfinite" figure is the count
            # of AFFECTED PARAMS. full mode pays a second elementwise
            # pass for exact per-param element counts (the debugging
            # mode; the 2% budget applies to step).
            f32 = jnp.float32
            sq, nf = [], []
            for n in update_names:
                g = gs[n]
                if jnp.issubdtype(g.dtype, jnp.inexact):
                    g32 = g.astype(f32)
                    sq.append(jnp.sum(jnp.square(g32)))
                    if numerics == "full":
                        nf.append(jnp.sum(~jnp.isfinite(g32))
                                  .astype(f32))
                else:
                    sq.append(jnp.zeros((), f32))
                    if numerics == "full":
                        nf.append(jnp.zeros((), f32))
            sq = jnp.stack(sq)
            loss = jnp.mean(outs[0]).astype(f32)
            bad = jnp.sum(jnp.stack(nf)) if numerics == "full" \
                else jnp.sum(~jnp.isfinite(sq)).astype(f32)
            head = jnp.stack([loss, jnp.sqrt(jnp.sum(sq)), bad])
            if numerics == "step":
                return head
            return jnp.concatenate([head, jnp.sqrt(sq), jnp.stack(nf)])

        def _update(genv, gs, senv, harr):
            new_p, new_s = {}, {}
            for i, n in enumerate(update_names):
                h = unpack_fused_hyper(harr[i], hyper_keys, genv[n].dtype)
                new_p[n], new_s[n] = rule(genv[n], gs[n], senv[n], h)
            return new_p, new_s

        def _core(genv, senv, harr, fenv, key, cts):
            def fwd(ge):
                env = dict(fenv)
                env.update(ge)
                return fn(env, key)     # -> (outputs, new_aux)

            outs, vjp_fn, new_aux = jax.vjp(fwd, genv, has_aux=True)
            if cts is None:
                cts = tuple(jnp.ones(o.shape, dtype=o.dtype) for o in outs)
            (gs,) = vjp_fn(tuple(cts))
            sentinel = _sentinel(gs, outs) if numerics != "off" else None
            new_p, new_s = _update(genv, gs, senv, harr)
            return new_p, new_s, new_aux, outs, sentinel

        def _accum_core(genv, senv, harr, fenv, key, mbenv):
            # Gradient accumulation INSIDE the donated program: a
            # lax.scan over the leading microbatch axis of ``mbenv``,
            # with the gradient accumulator as the carry, then ONE
            # optimizer-rule application on the total. The reduction
            # order is fixed and documented: microbatch 0 seeds the
            # accumulator (never zeros — IEEE `0.0 + (-0.0)` would
            # flip the sign bit of a -0.0 gradient) and microbatches
            # 1..A-1 fold in left-to-right, so a W-survivor world
            # reproduces the base world's per-step reduction as
            # (psum_W(mb0) + psum_W(mb1)) + ... — bitwise-stable
            # across rescales of the same global batch.
            def grads(a_env):
                def fwd(ge):
                    env = dict(fenv)
                    env.update(a_env)
                    env.update(ge)
                    return fn(env, key)
                outs, vjp_fn, _aux = jax.vjp(fwd, genv, has_aux=True)
                cts = tuple(jnp.ones(o.shape, dtype=o.dtype) for o in outs)
                (gs,) = vjp_fn(cts)
                return gs, outs

            g_tot, outs0 = grads({n: v[0] for n, v in mbenv.items()})
            if accum > 1:
                xs = {n: v[1:] for n, v in mbenv.items()}

                def body(acc, a_env):
                    ga, outs_a = grads(a_env)
                    return {n: acc[n] + ga[n] for n in acc}, outs_a

                g_tot, outs_rest = jax.lax.scan(body, g_tot, xs)
                outs = tuple(
                    jnp.concatenate([o0[None], rest], axis=0)
                    for o0, rest in zip(outs0, outs_rest))
            else:
                outs = tuple(o[None] for o in outs0)
            sentinel = _sentinel(g_tot, outs) if numerics != "off" else None
            new_p, new_s = _update(genv, g_tot, senv, harr)
            return new_p, new_s, {}, outs, sentinel

        if accum_names:
            def run(genv, senv, harr, fenv, key, mbenv):
                return _accum_core(genv, senv, harr, fenv, key, mbenv)
        elif default_ct:
            def run(genv, senv, harr, fenv, key):
                return _core(genv, senv, harr, fenv, key, None)
        else:
            def run(genv, senv, harr, fenv, key, cts):
                return _core(genv, senv, harr, fenv, key, cts)

        return jax.jit(run, donate_argnums=(0, 1) if donate else ())

    def train_step(self, rule, update_names, states, hyper, feed=None,
                   out_grads=None, accum_feed=None):
        """One fused XLA program per training step: forward + backward +
        optimizer update (+ gradient all-reduce under ``set_dp_mesh``,
        inserted by GSPMD inside the SAME program).

        Parameters
        ----------
        rule : pure ``(weight, grad, state_tuple, hyper) ->
            (new_weight, new_state_tuple)`` (``Optimizer.fused_rule()``).
        update_names : arg names to update; each must be bound with
            grad_req='write'.
        states : dict name -> tuple of NDArray optimizer-state buffers
            (``optimizer.fused_state_arrays``); updated in place.
        hyper : dict name -> dict of python scalars for ``rule``
            (``Optimizer.fused_hyper``), all with the same keys. Packed
            here into ONE float32 array (``optimizer.pack_fused_hyper``)
            and handed to the program as one traced argument: one
            host→device hand-over a step, and lr-schedule/rescale
            changes never recompile.
        feed : optional dict of input name -> NDArray/host array, staged
            like ``forward(**kwargs)``.
        out_grads : optional output cotangents (default: ones, matching
            ``backward(out_grads=None)``).

        Programs are cached per (rule, grad-name set, cotangent mode);
        jit re-specializes per shape signature. The step is ONE host
        dispatch — recorded as a single ``fused_train_step`` op in the
        telemetry dispatch counters (ops inside the program are invisible
        to the per-op eager counters by construction).
        """
        update_names = tuple(update_names)
        for n in update_names:
            if self._grad_req.get(n) != "write":
                raise MXNetError(
                    "train_step requires grad_req='write' for %r (got %r)"
                    % (n, self._grad_req.get(n)))
        accum = 1
        mbenv = None
        if accum_feed:
            # gradient-accumulation mode (elastic rescale / beyond-HBM
            # global batches): every data input arrives microbatched
            # [A, L, ...] through accum_feed, bypassing the bound
            # [L, ...] buffers entirely
            if out_grads is not None:
                raise MXNetError(
                    "train_step(accum_feed=...) supports only the "
                    "default cotangents (out_grads=None)")
            if self._aux_names:
                raise MXNetError(
                    "train_step(accum_feed=...) cannot honor aux "
                    "states (batch-norm running stats mutate per "
                    "microbatch, which breaks the bitwise global-batch "
                    "contract); aux-free graphs only")
            dims = {int(_np.shape(v)[0]) for v in accum_feed.values()}
            if len(dims) != 1:
                raise MXNetError(
                    "accum_feed entries disagree on the microbatch "
                    "count: %s" % sorted(dims))
            accum = dims.pop()
            for n in accum_feed:
                if n not in self.arg_dict:
                    raise MXNetError("unknown train_step input %r" % n)
            mbenv = {n: self._place_accum(n, v)
                     for n, v in accum_feed.items()}
        self._stage_inputs(feed)

        # donation honors the same knob as the per-param update kernels
        # (ops/registry.py _donation_allowed): with it off, pre-update
        # buffers held by external code stay valid on TPU
        from .config import get as _cfg
        donate = bool(_cfg("MXNET_UPDATE_BUFFER_DONATION"))
        numerics = _health.numerics_mode()
        accum_names = tuple(sorted(accum_feed)) if accum_feed else ()
        from .optimizer import pack_fused_hyper
        hyper_keys, harr = pack_fused_hyper(
            [hyper[n] for n in update_names])
        cache_key = (rule, update_names, out_grads is None, donate,
                     numerics, accum, accum_names, hyper_keys)

        env = self._env()
        genv = {n: env.pop(n) for n in update_names}
        if mbenv is not None:
            for n in accum_names:
                env.pop(n, None)      # traced via mbenv, not the binding
        senv = {}
        for n in update_names:
            tup = []
            for a in states[n]:
                d = a._data
                if self._dp_mesh is not None:
                    # states ride replicated, like the parameters; a
                    # cheap sharding-equality check steady-state
                    placed = self._dp_place(n, d)
                    if placed is not d:
                        a._set_data(placed)
                        d = placed
                tup.append(d)
            senv[n] = tuple(tup)
        key = _random.next_key() if self._needs_rng else None
        args = [genv, senv, harr, env, key]
        if mbenv is not None:
            args.append(mbenv)
        elif out_grads is not None:
            args.append(self._normalize_out_grads(out_grads))

        run = self._fused_jitted.get(cache_key)
        if run is None:
            install_donation_warning_filter()
            if self._dp_nproc > 1:
                # per-step accounting needs the gradient byte total on
                # registry hits too; the built-a-program counter and
                # the compile-attributed span are armed inside build()
                # below, so a program served from the process-wide
                # registry (zero builds) records neither
                self._allreduce_bytes = sum(
                    self.arg_dict[n]._data.nbytes for n in update_names)
            else:
                self._allreduce_bytes = 0
            # process-wide registry entry: a resumed trainer (or a
            # second Module over the same graph/optimizer) shares the
            # program, and the persistent compile cache makes the build
            # a disk load in a fresh process. A rule
            # that is a closure gets an instance salt — baked-in cell
            # contents have no stable cross-object identity
            rule_id = "%s.%s" % (getattr(rule, "__module__", "?"),
                                 getattr(rule, "__qualname__",
                                         type(rule).__name__))
            instance = None
            if getattr(rule, "__closure__", None) is not None:
                # one STABLE salt per (executor, rule object): a rebuild
                # after set_dp_mesh must re-hit the same registry entry
                # instead of pinning a duplicate donated program
                instance = self._rule_salts.get(rule)
                if instance is None:
                    instance = self._rule_salts[rule] = \
                        _pg.next_instance("rule")
            accum_sig = None
            if mbenv is not None:
                accum_sig = [[n, list(mbenv[n].shape), str(mbenv[n].dtype)]
                             for n in accum_names]
            pkey = _pg.ProgramKey(
                "fused_step", self._graph_hash,
                {"rule": rule_id, "update": list(update_names),
                 "default_ct": out_grads is None, "donate": donate,
                 "numerics": numerics, "args": self._buffer_sig(),
                 "mesh": self._mesh_sig(), "rng": self._needs_rng,
                 "accum": [accum, accum_sig] if accum_sig else None,
                 "hyper": list(hyper_keys)},
                instance=instance)
            built = []

            def build():
                built.append(True)
                if self._dp_nproc > 1:
                    # the cross-host gradient all-reduce is being
                    # traced INTO this program (GSPMD psum over the
                    # global mesh): count it at build time — there is
                    # no per-step host marker, by construction — and
                    # arm the one compile-time-attributed kv.allreduce
                    # span so traces show where the collective went
                    if _tm._enabled:
                        _tm.counter(
                            "kvstore/allreduce_programs_total",
                            "Fused train-step programs built with the "
                            "cross-host gradient all-reduce folded in "
                            "(dist_tpu_sync; GSPMD psum over the "
                            "global dp mesh)").inc()
                    self._allreduce_span_due = True
                if _tm._enabled:
                    _tm._ensure_compile_listener()
                    _tm.counter("executor/fused_step_compile_total",
                                "Fused train-step program builds "
                                "(fwd+bwd+update traced as one program)"
                                ).inc()
                return self._build_fused_step(
                    rule, update_names, out_grads is None, donate,
                    hyper_keys, numerics, accum=accum,
                    accum_names=accum_names)

            run = _pg.get_or_build(pkey, build)
            self._fused_jitted[cache_key] = run
            # roofline capture at compile time (HLO cost pass, NOT a
            # second backend compile; its pseudo-compile events are
            # suppressed from the telemetry counters)
            self._fused_costs[cache_key] = _pg.attach_cost(
                pkey, _health.capture_cost(
                    "fused_step", _health.next_cost_key("step"),
                    run, tuple(args), pkey=pkey))
            # the interval ending here includes trace+lower+compile:
            # never let it pollute the throughput-MFU gauge
            self._last_step_end = None
            if _tm._enabled:
                if built:
                    _tm.counter("executor/fused_step_cache_miss_total",
                                "Fused train-step calls that built a "
                                "new program").inc()
                else:
                    # local memo miss served by the process-wide
                    # registry: still a cache hit — hits + misses must
                    # account for every train_step program lookup
                    _tm.counter("executor/fused_step_cache_hit_total",
                                "Fused train-step calls served from "
                                "the program cache").inc()
        elif _tm._enabled:
            _tm.counter("executor/fused_step_cache_hit_total",
                        "Fused train-step calls served from the program "
                        "cache").inc()
        self._fused_cost_rec = self._fused_costs.get(cache_key)

        from . import engine as _engine
        from . import profiler as _prof
        token = _tm.dispatch_begin() if _tm._enabled else None
        with _tr.child_span("executor.train_step"):
            if getattr(self, "_allreduce_span_due", False):
                # compile-time-attributed marker: the in-program
                # collective has no per-step host span by construction
                # (that is the win), so the ONE span is recorded where
                # the psum is traced+compiled into the program — the
                # first dispatch after a build
                self._allreduce_span_due = False
                with _tr.child_span(
                        "kv.allreduce",
                        attrs={"bytes": self._allreduce_bytes,
                               "processes": self._dp_nproc,
                               "compile_attributed": True}):
                    new_p, new_s, new_aux, outs, sentinel = run(*args)
            elif _engine.profiling_imperative():
                with _prof.scope("fused_train_step", "executor"):
                    new_p, new_s, new_aux, outs, sentinel = run(*args)
            else:
                new_p, new_s, new_aux, outs, sentinel = run(*args)
        if token is not None:
            _tm.dispatch_end("fused_train_step", token)

        for n in update_names:
            self.arg_dict[n]._set_data(new_p[n])
            for tgt, val in zip(states[n], new_s[n]):
                tgt._set_data(val)
        for name, val in new_aux.items():
            self.aux_dict[name]._set_data(val)
        self.outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        if _tm._enabled:
            _tm.counter("executor/fused_step_total",
                        "Completed fused train steps").inc()
            _tm.counter("executor/fused_step_hyper_put_total",
                        "Host-to-device hand-overs of the fused step's "
                        "packed hyper-parameter array (one a step: a "
                        "step that hands over more has fallen back to "
                        "scalar leaves)").inc()
            if self._dp_nproc > 1:
                # in-program collective accounting: one allreduce rode
                # this step, over this many gradient bytes — and ZERO
                # bytes through any host socket (contrast
                # kvstore/bytes_total on the PS path)
                _tm.counter("kvstore/allreduce_steps_total",
                            "Fused train steps whose gradient "
                            "all-reduce ran in-program (dist_tpu_sync)"
                            ).inc()
                _tm.counter("kvstore/allreduce_bytes_total",
                            "Gradient bytes reduced by in-program "
                            "collectives (per step: sum of parameter "
                            "gradient sizes)").inc(self._allreduce_bytes)

        # throughput MFU: the interval between consecutive step ends is
        # the honest steady-state step wall (compute + whatever host
        # work the loop pays); combined with the program's measured
        # FLOPs it sets executor/mfu + executor/hbm_bw_util
        now = _tm.monotonic()
        last, self._last_step_end = self._last_step_end, now
        if last is not None and self._fused_cost_rec is not None:
            _health.note_executor_step(self._fused_cost_rec, now - last)

        # the sentinel verdict is read ONE step deferred: step N's
        # vector is fetched after step N+1 has been dispatched, so the
        # (tiny) D2H blocks only on a program that must already have
        # finished — the host/device pipeline never stalls and a trip
        # still surfaces within one step (flush_numerics() drains the
        # tail at epoch/run end)
        pending, self._pending_sentinel = self._pending_sentinel, None
        if sentinel is not None:
            self._pending_sentinel = (sentinel, numerics, update_names)
        if pending is not None:
            self._check_sentinel(*pending)
        return self.outputs

    def _check_sentinel(self, sentinel, numerics, update_names):
        """Read one step's packed sentinel vector (a single small D2H
        fetch — not an op dispatch, not a recompile:
        tests/test_health.py holds both counts) and
        apply the numerics policy."""
        from .parallel.mesh import host_local_value
        vals = _np.asarray(host_local_value(sentinel))
        report = {"loss": float(vals[0]),
                  "grad_norm": float(vals[1]),
                  "nonfinite": int(vals[2])}
        if numerics == "full":
            p = len(update_names)
            report["per_param"] = {
                n: {"norm": float(vals[3 + i]),
                    "nonfinite": int(vals[3 + p + i])}
                for i, n in enumerate(update_names)}
        _health.check_numerics(report, state=self._numerics_state)

    def flush_numerics(self):
        """Drain the deferred sentinel of the LAST fused step (applies
        the policy for a trip on a run's final step); called by
        ``Module.fit`` at each epoch end."""
        pending, self._pending_sentinel = self._pending_sentinel, None
        if pending is not None:
            self._check_sentinel(*pending)

    def fused_cost(self):
        """Cost-analysis record of the most recently used fused-step
        program ({'flops','bytes',...}), or None where the backend
        offers no analysis (``health.note_executor_step`` prices the
        ``executor/mfu`` gauge from it)."""
        return self._fused_cost_rec

    def forward_cost(self, is_train=False):
        """Cost-analysis record of the compiled forward program (the
        serve engine aliases this under its bucket for per-bucket
        MFU)."""
        return self._fwd_cost.get(bool(is_train))

    # -- parameter management ---------------------------------------------
    def alias_args(self, other, names):
        """Share argument/aux NDArray objects with another executor (the
        analog of the reference's shared-executor memory reuse,
        graph_executor.cc InitDataEntryMemory shared_exec path). Both
        executors then read and update the SAME buffers."""
        for n in names:
            if n in other.arg_dict:
                shared = other.arg_dict[n]
                idx = self._arg_names.index(n)
                self.arg_arrays[idx] = shared
                self.arg_dict[n] = shared
            elif n in other.aux_dict:
                idx = self._aux_names.index(n)
                self.aux_arrays[idx] = other.aux_dict[n]
                self.aux_dict[n] = other.aux_dict[n]

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        """Reference: executor.py copy_params_from."""
        for name, array in arg_params.items():
            if name in self.arg_dict:
                dst = self.arg_dict[name]
                array.astype(dst.dtype, copy=False).copyto(dst)
            elif not allow_extra_params:
                raise ValueError("Find name \"%s\" that is not in the arguments"
                                 % name)
        if aux_params is None:
            return
        for name, array in aux_params.items():
            if name in self.aux_dict:
                array.copyto(self.aux_dict[name])
            elif not allow_extra_params:
                raise ValueError("Find name %s that is not in the auxiliary "
                                 "states" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new input shapes (reference: executor.py reshape).
        Cheap here: jit re-specializes per shape signature automatically, so
        only the argument buffers need reallocating."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = []
        for name, shape, old in zip(self._arg_names, arg_shapes,
                                    self.arg_arrays):
            if shape == old.shape:
                new_args.append(old)
            else:
                new_args.append(zeros(shape, ctx=self._ctx, dtype=old.dtype))
        new_aux = []
        for shape, old in zip(aux_shapes, self.aux_arrays):
            new_aux.append(old if shape == old.shape
                           else zeros(shape, ctx=self._ctx, dtype=old.dtype))
        grad_req = {n: self._grad_req[n] for n in self._arg_names}
        return Executor(self._symbol, self._ctx, new_args,
                        grad_req=grad_req, aux_states=new_aux)

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_callback = callback

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def debug_str(self):
        lines = ["Symbol Outputs:"]
        for n in self._symbol.list_outputs():
            lines.append("\toutput[%s]" % n)
        return "\n".join(lines)
