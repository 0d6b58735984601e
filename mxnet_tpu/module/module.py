"""Module: symbol + one bound executor + optimizer state.

Reference: python/mxnet/module/module.py:259-644. The reference's
DataParallelExecutorGroup (executor_group.py:143) slices a batch over a
GPU list; the TPU-native equivalent is sharding the batch over a device
mesh — that path lives in ``mxnet_tpu.kvstore``/``mxnet_tpu.parallel``
(`dist_tpu_sync`), while Module itself binds ONE compiled executor (XLA
distributes over the mesh when the kvstore type asks for it).
"""
from __future__ import annotations

import logging
import warnings

from .. import context as ctx_mod
from .. import optimizer as opt
from ..base import MXNetError
from ..initializer import Uniform, InitDesc
from ..io import DataDesc
from ..model import (_create_kvstore, _initialize_kvstore,
                     _update_params, _update_params_on_kvstore,
                     fused_step_supported, load_checkpoint, BatchEndParam)
from ..ndarray.ndarray import NDArray, zeros
from .base_module import (BaseModule, _check_input_names, _parse_data_desc,
                          _as_list)

__all__ = ["Module"]


class Module(BaseModule):
    """Symbolic training module (reference: module.py:59)."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        if context is None:
            context = ctx_mod.current_context()
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        state_names = list(state_names) if state_names is not None else []
        fixed_param_names = (list(fixed_param_names)
                             if fixed_param_names is not None else [])
        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, state_names, "state", True)
        _check_input_names(symbol, fixed_param_names, "fixed_param", True)

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + state_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = state_names
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._compression_params = compression_params
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._fused_batch = None
        # >1 after an elastic rescale: each step runs this many
        # sequential gradient microbatches inside the fused program
        # (the per-rank batch is the base world's batch x accum)
        self._elastic_accum = 1

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create a module from a saved checkpoint (reference:
        module.py load)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        nbatch=0, io_cursor=None):
        """Save symbol json + params (+ optimizer states)
        (reference: module.py save_checkpoint → model.py:383).

        Crash-consistent: every file goes through the atomic
        write-temp→fsync→rename path and a ``.manifest.json`` sidecar
        records checksums, epoch/batch position, and RNG state, so a
        SIGKILL at any instant never clobbers the previous good
        checkpoint and ``checkpoint.load_latest_valid`` can verify this
        one. ``nbatch`` > 0 marks a mid-epoch (preemption) save."""
        from .. import telemetry as _tm
        from ..checkpoint import record_checkpoint_save, write_manifest
        t0 = _tm.monotonic()
        sym_file = "%s-symbol.json" % prefix
        self._symbol.save(sym_file)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        logging.info("Saved checkpoint to \"%s\"", param_name)
        state_name = None
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info("Saved optimizer state to \"%s\"", state_name)
        write_manifest(prefix, epoch,
                       {"params": param_name, "symbol": sym_file,
                        "states": state_name}, nbatch=nbatch,
                       extra={"io_cursor": io_cursor} if io_cursor else None)
        record_checkpoint_save(param_name, t0)

    # -- properties --------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        if self._exec.outputs:
            return [(n, tuple(o.shape))
                    for n, o in zip(self._output_names, self._exec.outputs)]
        # before the first forward, infer statically from the bound
        # input shapes (SequentialModule chains shapes at bind time)
        shape_kwargs = {d.name: d.shape for d in self._data_shapes}
        if self._label_shapes:
            shape_kwargs.update({l.name: l.shape
                                 for l in self._label_shapes})
        _, out_shapes, _ = self._symbol.infer_shape(**shape_kwargs)
        if out_shapes is None:
            return None
        return list(zip(self._output_names,
                        [tuple(s) for s in out_shapes]))

    # -- parameters --------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        """Initialize parameters (reference: module.py:259)."""
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False. "
                          "init_params call ignored.", stacklevel=2)
            return
        assert self.binded, "call bind before initializing the parameters"

        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(InitDesc(name, attrs.get(name)), arr)
            else:
                if initializer is not None:
                    initializer(InitDesc(name, attrs.get(name)), arr)

        for name in self._param_names:
            _impl(name, self._exec.arg_dict[name], arg_params)
        for name in self._aux_names:
            _impl(name, self._exec.aux_dict[name], aux_params)

        self.params_initialized = True
        self._params_dirty = True
        self._sync_params_from_devices()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False. "
                          "set_params call ignored.", stacklevel=2)
            return
        for name, arr in (arg_params or {}).items():
            if name in self._exec.arg_dict:
                arr.copyto(self._exec.arg_dict[name])
        for name, arr in (aux_params or {}).items():
            if name in self._exec.aux_dict:
                arr.copyto(self._exec.aux_dict[name])
        self.params_initialized = True
        self._params_dirty = True

    def _sync_params_from_devices(self):
        """Copy executor parameter values into the CPU-side dicts
        (reference: executor_group get_params)."""
        self._arg_params = {n: self._exec.arg_dict[n].copy()
                            for n in self._param_names}
        self._aux_params = {n: self._exec.aux_dict[n].copy()
                            for n in self._aux_names}
        self._params_dirty = False

    # -- binding -----------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Bind executors (reference: module.py:364)."""
        if force_rebind:
            self._exec = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        assert shared_module is None, \
            "shared_module not supported (XLA shares compiled code by shape)"

        self._data_shapes, self._label_shapes = _parse_data_desc(
            self._data_names, self._label_names, data_shapes, label_shapes)

        shape_kwargs = {d.name: d.shape for d in self._data_shapes}
        if self._label_shapes:
            shape_kwargs.update({l.name: l.shape for l in self._label_shapes})

        reqs = {}
        for name in self._symbol.list_arguments():
            if name in self._param_names:
                reqs[name] = ("null" if name in self._fixed_param_names
                              or not for_training else grad_req)
            elif name in self._data_names:
                reqs[name] = grad_req if inputs_need_grad else "null"
            else:
                reqs[name] = "null"

        ctx = self._context[0]
        type_dict = {}
        for d in self._data_shapes:
            type_dict[d.name] = d.dtype
        if self._label_shapes:
            for l in self._label_shapes:
                type_dict[l.name] = l.dtype
        self._exec = self._symbol.simple_bind(
            ctx, grad_req=reqs, type_dict=type_dict, **shape_kwargs)
        if len(self._context) > 1:
            self._install_dp_mesh()
        self.binded = True

        # re-install cached params into the fresh executor (the reference
        # copies _arg_params into the new exec group at bind, module.py:426)
        if self.params_initialized and self._arg_params is not None:
            self._exec.copy_params_from(self._arg_params, self._aux_params,
                                        allow_extra_params=True)

    def _install_dp_mesh(self):
        """Data-parallel execution over the context list — the
        TPU-native DataParallelExecutorGroup (reference:
        python/mxnet/module/executor_group.py:143): one compiled program
        over a 1-D 'dp' mesh, batch args sharded on dim 0, parameters
        replicated; GSPMD inserts the gradient all-reduce the reference
        ran through KVStore local/device (comm.h:451).

        Raises when the context list cannot be mapped onto distinct
        devices — a context list must never silently train on one
        device."""
        import numpy as np
        from jax.sharding import Mesh
        devices = [c.jax_device() for c in self._context]
        unique = list(dict.fromkeys(devices))
        if len(unique) != len(devices):
            raise MXNetError(
                "Module got %d contexts (%s) but they resolve to only %d "
                "distinct devices; data-parallel binding needs one device "
                "per context. Use fewer contexts or run under more devices."
                % (len(self._context), self._context, len(unique)))
        mesh = Mesh(np.array(unique), ("dp",))
        batch_names = list(self._data_names) + list(self._label_names)
        self._exec.set_dp_mesh(mesh, batch_names)

    def _install_dist_mesh(self, kvstore):
        """Pod-scale data parallelism for ``dist_tpu_sync``: ONE global
        1-D 'dp' mesh over every device of every process (built on the
        same set_dp_mesh machinery the local context-list path uses).
        Each process stages its LOCAL batch shard (per-host input
        sharding — pair the iterator with ``io.dist_parts()``); GSPMD
        folds the cross-host gradient all-reduce into the fused
        train-step program, so the socket parameter server is off the
        hot path entirely."""
        from .. import telemetry as _tm
        from ..parallel.mesh import global_dp_mesh
        mesh = global_dp_mesh()
        batch_names = list(self._data_names) + list(self._label_names)
        self._exec.set_dp_mesh(mesh, batch_names)
        self.logger.info(
            "dist_tpu_sync: global dp mesh over %d devices / %d "
            "processes (rank %d); gradient all-reduce runs in-program",
            mesh.shape["dp"], kvstore.num_workers, kvstore.rank)
        if _tm._enabled:
            _tm.gauge("kvstore/dist_mesh_devices",
                      "Devices in the dist_tpu_sync global dp mesh"
                      ).set(mesh.shape["dp"])

    # -- optimizer ---------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Install optimizer + kvstore (reference: module.py:474)."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._params_dirty:
            self._sync_params_from_devices()

        # Module binds ONE executor however long the context list: over
        # a dp mesh its gradients arrive already all-reduced (GSPMD), so
        # a local/device kvstore has one device to see and nothing to
        # reduce — the update stays in the fused step
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, 1, self._arg_params)
        batch_size = self._data_shapes[0].shape[0]
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        idx2name = {i: n for i, n in enumerate(self._param_names)}
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                warnings.warn(
                    "Optimizer created manually outside Module but rescale_grad "
                    "is not normalized to 1.0/batch_size/num_workers (%s vs. %s). "
                    "Is this intended?" % (optimizer.rescale_grad, rescale_grad),
                    stacklevel=2)
            if not optimizer.idx2name:
                optimizer.idx2name = idx2name.copy()

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=[self._exec.arg_dict[n]
                                              for n in self._param_names],
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
            if kvstore.type == "dist_tpu_sync" and kvstore.num_workers > 1:
                # the global mesh makes the backward produce ALREADY
                # all-reduced gradients — only correct when the fused
                # step consumes them in-program. A config the fused
                # path can't take (MXNET_FUSED_STEP=0, optimizer
                # without a pure rule, compression, ...) stays on the
                # per-process local executor: its local gradients ride
                # kvstore.push → _cross_process_allreduce, the
                # host-driven fallback docs/distributed_training.md
                # documents (pushing mesh-reduced gradients through
                # that path would reduce them twice)
                if fused_step_supported(self._optimizer, kvstore,
                                        update_on_kvstore,
                                        self._compression_params) \
                        and self._exec._monitor_callback is None \
                        and not self.inputs_need_grad:
                    self._install_dist_mesh(kvstore)
                else:
                    self.logger.warning(
                        "dist_tpu_sync: configuration cannot take the "
                        "fused in-program-collective step; training "
                        "host-driven (per-gradient device allreduce, "
                        "no socket PS)")
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)

        self.optimizer_initialized = True

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    # -- computation -------------------------------------------------------
    def _build_feed(self, data_batch):
        """Executor input dict for a DataBatch (shared by the unfused
        forward and the fused train step, so both paths stage identical
        inputs)."""
        feed = {}
        for name, arr in zip(self._data_names, data_batch.data):
            feed[name] = arr
        if self._label_shapes and data_batch.label:
            for name, arr in zip(self._label_names, data_batch.label):
                feed[name] = arr
        return feed

    def forward(self, data_batch, is_train=None):
        """Forward (reference: module.py:589). Reshape-on-the-fly is free:
        jit respecializes per shape signature."""
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        self._exec.forward(is_train=is_train, **self._build_feed(data_batch))
        self._params_dirty = True

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    # -- fused train step --------------------------------------------------
    def _fused_step_ok(self):
        """True when forward+backward+update may run as ONE donated XLA
        program (Executor.train_step). Falls back for server-side /
        dist_* kvstore updates, gradient compression, optimizers without
        a pure rule, multi-precision, monitors (which need per-op
        outputs), input gradients, and non-'write' grad_req."""
        if not (self.binded and self.params_initialized
                and self.optimizer_initialized):
            return False
        if not fused_step_supported(self._optimizer, self._kvstore,
                                    self._update_on_kvstore,
                                    self._compression_params):
            return False
        if not isinstance(self._updater, opt.Updater):
            return False
        if self._exec._monitor_callback is not None or self.inputs_need_grad:
            return False
        for name in self._param_names:
            if self._exec._grad_req.get(name, "null") not in ("write",
                                                              "null"):
                return False
        return True

    def prepare(self, data_batch, sparse_row_id_fn=None):
        """Put the NEXT step's batch on the device(s) ahead of the step
        (``fit`` calls this right after step N was dispatched, with
        batch N+1). Where that step will run fused, its inputs go to
        ``Executor.prestage``: placed now, bound by the step itself if
        it is handed the very same buffers (docs/input_pipeline.md).
        Otherwise (a list of batches, the unfused path, elastic
        gradient accumulation) nothing happens, as before. No bound
        array, no output and no deferred batch is touched."""
        if (isinstance(data_batch, list) or self._elastic_accum != 1
                or not self._fused_step_ok()):
            return
        self._exec.prestage(self._build_feed(data_batch))

    def forward_backward(self, data_batch):
        """Forward + backward; when the fused step is engaged the batch
        is deferred and the whole step (forward, gradients, optimizer
        update) runs as one XLA program inside the following
        ``update()`` call, which binds the inputs ``prepare`` placed
        ahead when this is the batch it was given, and stages them
        itself when not — outputs become available after it, and the
        per-parameter gradient buffers (``_exec.grad_dict``) are NOT
        materialized: gradients exist only inside the program. Reading
        ``get_outputs()`` before ``update()`` replays the batch unfused
        (exact legacy semantics, including grad_dict); code that needs
        host-visible gradients every step should disable the fused path
        (``MXNET_FUSED_STEP=0``)."""
        if not isinstance(data_batch, list) and self._fused_step_ok():
            self._fused_batch = data_batch
            return
        # a batch deferred by an earlier call must not survive into the
        # next update() once the unfused path runs — it would replay the
        # stale batch over this one's gradients
        self._fused_batch = None
        super().forward_backward(data_batch)

    def _run_fused_step(self, data_batch):
        """Execute one fused train step on ``data_batch`` through
        Executor.train_step, keeping the Updater's per-index state dict
        (save/load_optimizer_states) as the source of truth."""
        exe = self._exec
        optimizer = self._optimizer
        updater = self._updater
        feed = self._build_feed(data_batch)
        update_names, states, hyper = [], {}, {}
        for i, name in enumerate(self._param_names):
            if exe._grad_req.get(name, "null") == "null":
                continue
            weight = exe.arg_dict[name]
            update_names.append(name)
            states[name] = opt.fused_state_arrays(
                updater.ensure_state(i, weight))
            hyper[name] = optimizer.fused_hyper(i)
        accum = int(self._elastic_accum)
        if accum > 1:
            # elastic mode: the local batch [A*L, ...] is A microbatches
            # of the BASE world's per-rank batch L, run sequentially
            # inside the program with a fixed accumulation order (the
            # bitwise-continuation contract, see Executor.train_step)
            import numpy as _np
            mb = {}
            for name, arr in feed.items():
                v = arr.asnumpy() if hasattr(arr, "asnumpy") \
                    else _np.asarray(arr)
                if v.shape[0] % accum:
                    raise MXNetError(
                        "elastic accum: batch dim %d of '%s' is not "
                        "divisible by accum factor %d"
                        % (v.shape[0], name, accum))
                mb[name] = v.reshape((accum, v.shape[0] // accum)
                                     + v.shape[1:])
            exe.train_step(optimizer.fused_rule(), tuple(update_names),
                           states, hyper, accum_feed=mb)
        else:
            exe.train_step(optimizer.fused_rule(), tuple(update_names),
                           states, hyper, feed=feed)

    def update(self):
        """Apply optimizer to gradients (reference: module.py:644 →
        model.py _update_params(_on_kvstore)). With a deferred fused
        batch pending, runs the whole step as one program instead."""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        self._params_dirty = True
        data_batch, self._fused_batch = self._fused_batch, None
        if data_batch is not None:
            if self._fused_step_ok():
                self._run_fused_step(data_batch)
                return
            # configuration changed between forward_backward and update
            # (e.g. fused path disabled): replay the unfused sequence
            self.forward(data_batch, is_train=True)
            self.backward()
        if getattr(self._exec, "_dp_nproc", 1) > 1:
            # the global dist mesh is installed, so these gradients are
            # ALREADY all-reduced by the backward; pushing them through
            # the kvstore would reduce them a second time. Reachable
            # only when the config degraded AFTER init_optimizer gated
            # the mesh install (e.g. a monitor installed mid-training).
            raise MXNetError(
                "dist_tpu_sync: the fused-step configuration changed "
                "after the global mesh was installed (monitor / "
                "grad_req / MXNET_FUSED_STEP?); the unfused update "
                "path cannot run over mesh-reduced gradients — "
                "restore the configuration or set it before "
                "init_optimizer")
        param_arrays = [self._exec.arg_dict[n] for n in self._param_names]
        grad_arrays = [self._exec.grad_dict[n] for n in self._param_names]
        if self._update_on_kvstore:
            _update_params_on_kvstore(param_arrays, grad_arrays,
                                      self._kvstore, self._param_names)
        else:
            _update_params(param_arrays, grad_arrays, updater=self._updater,
                           num_device=len(self._context),
                           kvstore=self._kvstore,
                           param_names=self._param_names)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._fused_batch is not None:
            # a caller inspecting outputs between forward_backward() and
            # update() gets exact legacy semantics: replay the deferred
            # batch unfused (outputs + grads materialize; the following
            # update() takes the legacy per-param path)
            batch, self._fused_batch = self._fused_batch, None
            self.forward(batch, is_train=True)
            self.backward()
        return list(self._exec.outputs)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized \
            and self.inputs_need_grad
        return [self._exec.grad_dict[n] for n in self._data_names]

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        outputs = self.get_outputs()
        if self._elastic_accum > 1 and outputs:
            # accum outputs are stacked [A, world*L, ...]; the metric
            # contract is flat local rows matching the local labels
            # [A*L, ...] — take this host's view and flatten the
            # microbatch dim back into the batch dim
            from ..ndarray.ndarray import array as _arr
            flat = []
            for o in outputs:
                loc = o.asnumpy()
                flat.append(_arr(loc.reshape((-1,) + loc.shape[2:]))
                            if loc.ndim >= 2 else o)
            outputs = flat
        eval_metric.update(labels, outputs)

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)

    # -- optimizer state io ------------------------------------------------
    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            from ..checkpoint import atomic_writer
            with atomic_writer(fname) as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())

    def reshape(self, data_shapes, label_shapes=None):
        """Reshape input shapes (reference: module.py reshape). jit
        re-specializes per shape, so only descriptors change."""
        assert self.binded
        self._data_shapes, self._label_shapes = _parse_data_desc(
            self._data_names, self._label_names, data_shapes, label_shapes)
        self._exec.drop_prestaged()

    def borrow_optimizer(self, shared_module):
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True

    # -- elastic rescale (checkpoint-free, driven by BaseModule.fit) -------
    def elastic_snapshot(self):
        """Host-side mirror of everything a checkpoint-free rescale
        carries across the runtime teardown: parameters, auxiliary
        states, optimizer state, and the optimizer's schedule counters.
        Pure host copies — after a peer death the device arrays
        (donated into the global mesh) are poisoned, so the
        step-boundary mirror is the only recoverable truth."""
        assert self.binded and self.params_initialized
        exe = self._exec
        snap = {"arg_params": {n: exe.arg_dict[n].asnumpy().copy()
                               for n in self._param_names},
                "aux_params": {n: exe.aux_dict[n].asnumpy().copy()
                               for n in self._aux_names}}
        if self._updater is not None:
            snap["updater"] = self._updater.get_states(dump_optimizer=False)
        if self._optimizer is not None:
            snap["opt_counts"] = dict(self._optimizer._index_update_count)
            snap["num_update"] = int(self._optimizer.num_update)
        return snap

    def elastic_restore(self, snapshot, data_shapes, label_shapes=None,
                        kvstore="dist_tpu_sync", accum=1):
        """Rebuild this module on the CURRENT (post-``dist_runtime.
        reinit``) runtime from an :meth:`elastic_snapshot`: fresh
        executor over the new global mesh, parameters and optimizer
        state from the mirror, gradient-accumulation factor ``accum``.
        The optimizer INSTANCE is kept and its lr-schedule counters are
        restored from the mirror, so the re-executed step sees exactly
        the schedule the unfaulted twin saw."""
        from ..ndarray.ndarray import array as _arr
        optimizer = self._optimizer
        self._elastic_accum = int(accum)
        self._fused_batch = None
        # host mirrors become the bind-time source of truth — the old
        # _arg_params wrap device buffers of the torn-down runtime
        self._arg_params = {k: _arr(v)
                            for k, v in snapshot["arg_params"].items()}
        self._aux_params = {k: _arr(v)
                            for k, v in snapshot["aux_params"].items()}
        self._params_dirty = False
        self.bind(data_shapes=data_shapes, label_shapes=label_shapes,
                  for_training=True, force_rebind=True)
        self.optimizer_initialized = False
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            force_init=True)
        if snapshot.get("updater") is not None and self._updater is not None:
            self._updater.set_states(snapshot["updater"])
        if snapshot.get("opt_counts") is not None and optimizer is not None:
            optimizer._index_update_count = dict(snapshot["opt_counts"])
            optimizer.num_update = int(snapshot.get("num_update",
                                                    optimizer.num_update))
