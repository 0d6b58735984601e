"""BaseModule: the high-level train/predict interface.

Reference: python/mxnet/module/base_module.py (fit at :410, the first
judged milestone of SURVEY.md §7 stage 4).
"""
from __future__ import annotations

import logging
import os
import signal
import threading
import time

from .. import fault as _fault
from .. import goodput as _gp
from .. import health as _health
from .. import metric as _metric
from .. import io as _io
from .. import tracing as _tr
from ..base import MXNetError
from ..initializer import Uniform
from ..ndarray.ndarray import NDArray

__all__ = ["BaseModule"]


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [a for a in args
                      if not a.endswith(("_weight", "_bias", "_gamma", "_beta"))]
        msg = ("\033[91mYou created Module with Module(..., %s_names=%s) but "
               "input with name '%s' is not found in symbol.list_arguments(). "
               "Did you mean one of:\n\t%s\033[0m"
               % (typename, str(names), name, "\n\t".join(candidates)))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


def _parse_data_desc(data_names, label_names, data_shapes, label_shapes):
    data_shapes = [x if isinstance(x, _io.DataDesc) else _io.DataDesc(*x)
                   for x in data_shapes]
    _check_names_match(data_names, data_shapes, "data", True)
    if label_shapes is not None:
        label_shapes = [x if isinstance(x, _io.DataDesc) else _io.DataDesc(*x)
                        for x in label_shapes]
        _check_names_match(label_names, label_shapes, "label", False)
    else:
        _check_names_match(label_names, [], "label", False)
    return data_shapes, label_shapes


def _check_names_match(data_names, data_shapes, name, throw):
    actual = [x[0] for x in data_shapes]
    if sorted(data_names) != sorted(actual):
        msg = "Data provided by %s_shapes don't match names specified by " \
              "%s_names (%s vs. %s)" % (name, name, str(data_shapes),
                                        str(data_names))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]


class BaseModule(object):
    """Base class for modules (reference: base_module.py:64)."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high-level API ----------------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0, sparse_row_id_fn=None):
        """Evaluate on ``eval_data`` (reference: base_module.py:210)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            if isinstance(eval_batch, list):
                self.update_metric(eval_metric,
                                   [eb.label for eb in eval_batch],
                                   pre_sliced=True)
            else:
                self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = _BatchEndParam(epoch=epoch, nbatch=nbatch,
                                        eval_metric=eval_metric, locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(params)
            actual_num_batch += 1
        if score_end_callback:
            params = _BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                    eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield outputs, nbatch, eval_batch

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Run prediction, collecting outputs
        (reference: base_module.py:321)."""
        import numpy as np
        from ..ndarray.ndarray import array
        assert self.binded and self.params_initialized
        if isinstance(eval_data, (NDArray, np.ndarray)):
            if isinstance(eval_data, np.ndarray):
                eval_data = array(eval_data)
            self.forward(_io.DataBatch([eval_data]))
            return self.get_outputs()[0]
        if not isinstance(eval_data, _io.DataIter):
            raise ValueError("eval_data must be of type NDArray or DataIter")
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    "Cannot merge batches, as num of outputs is not the same " \
                    "in mini-batches. Maybe bucketing is used?"
            output_list2 = [
                array(np.concatenate(
                    [out[i].asnumpy() for out in output_list]))
                for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None, checkpoint_prefix=None,
            checkpoint_period=1, save_optimizer_states=True, resume=False):
        """The full training loop (reference: base_module.py:410; loop body
        forward_backward/update at :528-529).

        Fault tolerance (beyond the reference): with
        ``checkpoint_prefix`` set, fit writes a crash-consistent
        checkpoint (params + optimizer state + manifest carrying the
        epoch/batch position and RNG state) every ``checkpoint_period``
        epochs, and a SIGTERM — the preemption notice on TPU VMs —
        takes a final mid-epoch checkpoint within the
        ``MXNET_CKPT_GRACE_S`` grace window before stopping. With
        ``resume=True`` fit restores the newest *valid* checkpoint
        under the prefix (torn/corrupt ones are skipped) and continues
        from the exact epoch + batch with the optimizer and RNG state
        of the interrupted run, so the post-resume trajectory is
        bitwise-identical to the uninterrupted one — provided the data
        iterator replays deterministically (no unseeded shuffling).
        """
        assert num_epoch is not None, "please specify number of epochs"

        resume_state = None
        skip_nbatch = 0
        io_seeked = False
        if resume:
            if checkpoint_prefix is None:
                raise MXNetError(
                    "fit(resume=True) needs checkpoint_prefix to know "
                    "where the checkpoints live")
            from ..checkpoint import load_latest_valid
            resume_state = load_latest_valid(checkpoint_prefix)
            if resume_state is not None:
                arg_params = resume_state.arg_params
                aux_params = resume_state.aux_params
                allow_missing = False
                begin_epoch = resume_state.epoch
                skip_nbatch = resume_state.nbatch
                # seek the data iterator via the manifest's shard cursor
                # when it supports it: O(1), nothing decoded on the way,
                # and the shuffle seed travels with the cursor so the
                # post-resume batch stream is bitwise-identical to the
                # uninterrupted run. Iterators without a cursor (or a
                # cursor from a different stream) fall back to replay.
                cur = resume_state.io_cursor
                if cur and hasattr(train_data, "restore_state"):
                    try:
                        train_data.restore_state(cur)
                        io_seeked = True
                    except MXNetError as e:
                        self.logger.warning(
                            "io cursor in %s-%04d does not fit this "
                            "iterator (%s); replaying the epoch instead",
                            checkpoint_prefix, resume_state.epoch, e)
                self.logger.info(
                    "resuming from checkpoint %s-%04d (epoch %d, "
                    "batch %d%s)", checkpoint_prefix, resume_state.epoch,
                    resume_state.epoch, resume_state.nbatch,
                    ", iterator seeked" if io_seeked else "")

        # -- elastic dist_tpu_sync (checkpoint-free rescale) ---------------
        # JOIN mode: a relaunched rank asks the running world for
        # admission BEFORE binding — the adopted plan brings the
        # runtime up against the new coordinator and positions the
        # (resharded) iterator at the agreed step; the kvstore init
        # broadcast below then pulls the survivors' parameters.
        from ..config import get as _cfg
        _elastic = None
        _el = None
        _el_root = str(_cfg("MXNET_ELASTIC_DIR") or "")
        if _el_root and int(_cfg("MXNET_ELASTIC_JOIN") or 0):
            from .. import elastic as _el
            _elastic, begin_epoch, skip_nbatch = _el.ElasticFit.join(
                train_data)
            io_seeked = True
            self.logger.info(
                "elastic: joined world=%d as rank %d, resuming at "
                "epoch %d batch %d", _elastic.agent.world,
                _elastic.agent.rank, begin_epoch, skip_nbatch)

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        _kv_obj = getattr(self, "_kvstore", None)
        if _el_root and _kv_obj is not None and \
                getattr(_kv_obj, "type", "") == "dist_tpu_sync" and \
                hasattr(self, "elastic_snapshot"):
            if _el is None:
                from .. import elastic as _el
            if _elastic is None:
                _elastic = _el.ElasticFit.for_world(self, train_data,
                                                    _kv_obj)
            _elastic.after_init(self, begin_epoch, skip_nbatch)
        elif _elastic is not None:
            raise MXNetError(
                "elastic join mode needs a dist_tpu_sync kvstore with "
                "a fused-step-capable module (got kvstore %r)"
                % getattr(_kv_obj, "type", kvstore))
        _rescale_errors = _el.rescale_errors() if _elastic is not None \
            else ()

        if resume_state is not None:
            # a module whose params were already live before this fit
            # (in-process re-fit after a caught interruption) must still
            # take the CHECKPOINT's params: init_params above ignores
            # its cache once params_initialized, set_params(force_init)
            # does not — params, optimizer state, and RNG must all come
            # from the same checkpoint or resume is silently mixed
            self.set_params(resume_state.arg_params,
                            resume_state.aux_params, force_init=True)
            if resume_state.states_fname and \
                    hasattr(self, "load_optimizer_states"):
                self.load_optimizer_states(resume_state.states_fname)
            if resume_state.rng is not None:
                from .. import random as _random
                _random.set_state(resume_state.rng)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)

        # SIGTERM = preemption notice: checkpoint within the grace
        # window, then stop. The watchdog hard-exits at grace end —
        # the platform reclaims the VM then regardless, and a hung
        # save must not make the process outstay the notice.
        preempt = {"flag": False, "watchdog": None}
        prev_handler = None
        if checkpoint_prefix is not None and \
                threading.current_thread() is threading.main_thread():
            def _on_sigterm(signum, frame):
                if preempt["flag"]:
                    return
                preempt["flag"] = True
                from ..config import get as _cfg
                grace = float(_cfg("MXNET_CKPT_GRACE_S"))
                if grace > 0:
                    t = threading.Timer(grace, os._exit, args=(143,))
                    t.daemon = True
                    t.start()
                    preempt["watchdog"] = t
                self.logger.info("SIGTERM: checkpointing and stopping "
                                 "within the %.0fs grace window", grace)
            try:
                prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
            except ValueError:
                prev_handler = None

        # goodput ledger: attribute every wall-second of this fit to one
        # category (step compute / data wait / compile / checkpoint /
        # rescale / restart / straggler wait / idle) — pure host
        # arithmetic, zero device dispatches (goodput.py)
        _gp.session_begin()

        try:
            while True:
                try:
                    for epoch in range(begin_epoch, num_epoch):
                        tic = time.time()
                        eval_metric.reset()
                        nbatch = 0
                        data_iter = iter(train_data)
                        if skip_nbatch:
                            if io_seeked:
                                # the iterator is already at the cursor; only
                                # the batch numbering needs to line up
                                nbatch = skip_nbatch
                            else:
                                # mid-epoch resume without a seekable cursor:
                                # draw and discard the batches the interrupted
                                # run already trained on, so the iterator
                                # position and batch numbering line up with the
                                # uninterrupted run
                                for _ in range(skip_nbatch):
                                    try:
                                        next(data_iter)
                                    except StopIteration:
                                        break
                                    nbatch += 1
                            skip_nbatch = 0
                        io_seeked = False
                        end_of_batch = False
                        eval_name_vals = eval_metric.get_name_value()
                        try:
                            next_data_batch = next(data_iter)
                            # the epoch's first batch is prepared where
                            # it is fetched, as every later one is
                            self.prepare(next_data_batch,
                                         sparse_row_id_fn=sparse_row_id_fn)
                        except StopIteration:
                            end_of_batch = True
                        while not end_of_batch:
                            data_batch = next_data_batch
                            _fault.inject("engine.step")
                            if _elastic is not None:
                                # raises MembershipChange on a stale
                                # peer heartbeat or a pending joiner
                                _elastic.pre_step(epoch, nbatch)
                            _gp_tok = _gp.step_begin()
                            _gp_dw = 0.0
                            # per-step trace timeline: one root span per loop
                            # iteration (head-sampled), callbacks included, so
                            # that its children tile the step — was it waiting
                            # on data, on forward-backward, on the optimizer,
                            # on the metric's fetch or on a callback?
                            with _tr.start_span("train.step",
                                                attrs={"epoch": epoch,
                                                       "nbatch": nbatch}):
                                if monitor is not None:
                                    monitor.tic()
                                try:
                                    with _tr.child_span("train.forward_backward"):
                                        self.forward_backward(data_batch)
                                    with _tr.child_span("train.update"):
                                        if _elastic is not None:
                                            # step watchdog: a peer dying
                                            # mid-collective can park this
                                            # call forever on TPU
                                            _elastic.run_update()
                                        else:
                                            self.update()
                                except _health.NumericsError:
                                    # policy checkpoint-and-raise: preserve the
                                    # tripped state under a FORENSIC prefix (the
                                    # nonfinite params are the blast-radius
                                    # evidence) without clobbering the recovery
                                    # chain load_latest_valid walks, then stop
                                    if (checkpoint_prefix is not None
                                            and _health.numerics_policy()
                                            == "checkpoint-and-raise"):
                                        self._save_fit_checkpoint(
                                            checkpoint_prefix + ".numerics",
                                            epoch, nbatch + 1,
                                            save_optimizer_states, train_data)
                                    raise
                                # batch N+1 is fetched and handed to
                                # prepare() HERE, behind step N's dispatch
                                # and before the metric below waits on the
                                # device: its H2D copy then runs beside the
                                # step (Module.prepare). data_batch stays
                                # step N's until the loop's top
                                fetched = None
                                with _tr.child_span("train.data_wait"):
                                    _gp_dw = time.perf_counter()
                                    try:
                                        fetched = next(data_iter)
                                    except StopIteration:
                                        end_of_batch = True
                                    _gp_dw = time.perf_counter() - _gp_dw
                                if fetched is not None:
                                    next_data_batch = fetched
                                    try:
                                        self.prepare(
                                            next_data_batch,
                                            sparse_row_id_fn=sparse_row_id_fn)
                                    except StopIteration:
                                        end_of_batch = True
                                with _tr.child_span("train.update_metric"):
                                    if isinstance(data_batch, list):
                                        self.update_metric(
                                            eval_metric,
                                            [db.label for db in data_batch],
                                            pre_sliced=True)
                                    else:
                                        self.update_metric(eval_metric,
                                                           data_batch.label)
                                if _elastic is not None:
                                    # the metric sync above proved the
                                    # step's arrays are materialized:
                                    # vote it completed and refresh the
                                    # host param mirror survivors would
                                    # restore from
                                    _elastic.note_step(epoch, nbatch + 1)
                                _gp.step_end(_gp_tok, data_wait_s=_gp_dw)
                                if monitor is not None:
                                    monitor.toc_print()
                                if end_of_batch:
                                    eval_name_vals = \
                                        eval_metric.get_name_value()
                                if batch_end_callback is not None:
                                    params = _BatchEndParam(
                                        epoch=epoch, nbatch=nbatch,
                                        eval_metric=eval_metric,
                                        locals=locals())
                                    with _tr.child_span("train.callbacks"):
                                        for callback in _as_list(
                                                batch_end_callback):
                                            callback(params)
                            nbatch += 1
                            if preempt["flag"]:
                                if end_of_batch:
                                    self._save_fit_checkpoint(
                                        checkpoint_prefix, epoch + 1, 0,
                                        save_optimizer_states, train_data)
                                else:
                                    self._save_fit_checkpoint(
                                        checkpoint_prefix, epoch, nbatch,
                                        save_optimizer_states, train_data)
                                if preempt["watchdog"] is not None:
                                    preempt["watchdog"].cancel()
                                self.logger.info(
                                    "preemption checkpoint saved at epoch %d "
                                    "batch %d; stopping fit (resume=True picks "
                                    "up here)", epoch, nbatch)
                                return

                        # drain the deferred numerics sentinel of the epoch's
                        # final step (its verdict is read one step behind so
                        # the device pipeline never stalls)
                        try:
                            self._flush_numerics()
                        except _health.NumericsError:
                            if (checkpoint_prefix is not None
                                    and _health.numerics_policy()
                                    == "checkpoint-and-raise"):
                                self._save_fit_checkpoint(
                                    checkpoint_prefix + ".numerics", epoch,
                                    nbatch, save_optimizer_states, train_data)
                            raise

                        for name, val in eval_name_vals:
                            self.logger.info("Epoch[%d] Train-%s=%f", epoch, name,
                                             val)
                        toc = time.time()
                        self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                                         (toc - tic))

                        arg_p, aux_p = self.get_params()
                        self.set_params(arg_p, aux_p)
                        if epoch_end_callback is not None:
                            for callback in _as_list(epoch_end_callback):
                                callback(epoch, self.symbol, arg_p, aux_p)
                        if checkpoint_prefix is not None and \
                                (epoch + 1) % checkpoint_period == 0:
                            self._save_fit_checkpoint(checkpoint_prefix, epoch + 1,
                                                      0, save_optimizer_states,
                                                      train_data)

                        if eval_data is not None:
                            res = self.score(eval_data, validation_metric,
                                             score_end_callback=eval_end_callback,
                                             batch_end_callback=eval_batch_end_callback,
                                             epoch=epoch)
                            for name, val in res:
                                self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                                 name, val)
                        train_data.reset()
                except _rescale_errors as _mchange:
                    # a membership change (dead peer, stalled
                    # collective, pending joiner): run the rescale
                    # barrier, rebuild on the surviving mesh, and
                    # re-enter the loop at the agreed step
                    begin_epoch, skip_nbatch = _elastic.handle(_mchange)
                    io_seeked = True
                    continue
                break
        finally:
            _gp.session_end()
            if prev_handler is not None:
                signal.signal(signal.SIGTERM, prev_handler)
            if preempt["watchdog"] is not None:
                preempt["watchdog"].cancel()
            if _elastic is not None:
                _elastic.stop()
            # deterministic teardown of prefetch threads / decode
            # workers (close() is restartable, so handing the same
            # iterator to a second fit still works)
            for it in (train_data, eval_data):
                closer = getattr(it, "close", None)
                if callable(closer):
                    try:
                        closer()
                    except Exception:
                        self.logger.warning(
                            "data iterator close() failed", exc_info=True)

    def _save_fit_checkpoint(self, prefix, epoch, nbatch,
                             save_optimizer_states, train_data=None):
        """One crash-consistent fit checkpoint: params + optimizer state
        + manifest (epoch/batch position, RNG state, and — when the
        iterator supports it — the resumable shard cursor). Numbered by
        completed epochs; a mid-epoch save reuses the epoch number with
        ``nbatch`` > 0 and supersedes that epoch's boundary save."""
        io_cursor = None
        cursor_fn = getattr(train_data, "checkpoint_state", None)
        if callable(cursor_fn):
            try:
                io_cursor = cursor_fn(epoch, nbatch)
            except Exception:
                self.logger.warning(
                    "data iterator checkpoint_state() failed; checkpoint "
                    "carries no io cursor (resume will replay)",
                    exc_info=True)
        _gp_t0 = time.perf_counter()
        try:
            with _tr.start_span("train.checkpoint",
                                attrs={"epoch": epoch, "nbatch": nbatch}):
                saver = getattr(self, "save_checkpoint", None)
                if saver is not None:
                    saver(prefix, epoch, save_optimizer_states, nbatch=nbatch,
                          io_cursor=io_cursor)
                    return
                # modules without a save_checkpoint of their own
                # (Sequential, Python): params + manifest through the
                # model-level writer
                from ..model import save_checkpoint as _model_save
                arg_p, aux_p = self.get_params()
                states = None
                if save_optimizer_states and self.optimizer_initialized and \
                        hasattr(self, "save_optimizer_states"):
                    states = "%s-%04d.states" % (prefix, epoch)
                    self.save_optimizer_states(states)
                _model_save(prefix, epoch, self._symbol, arg_p, aux_p,
                            nbatch=nbatch, states_fname=states,
                            io_cursor=io_cursor)
        finally:
            _gp.note("checkpoint", time.perf_counter() - _gp_t0)

    # -- properties --------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    # -- parameters --------------------------------------------------------
    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        from ..ndarray import save
        save(fname, save_dict)

    def load_params(self, fname):
        from ..ndarray import load
        save_dict = load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        assert not merge_multi_context
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        assert not states and not value

    def install_monitor(self, mon):
        raise NotImplementedError()

    # -- computation -------------------------------------------------------
    def prepare(self, data_batch, sparse_row_id_fn=None):
        """Prepare for processing ``data_batch``, the batch of the NEXT
        step (row-sparse pull hook in the reference). ``fit`` calls it
        as soon as that batch is fetched: for an epoch's first batch
        before the first step, after that right behind step N's
        ``update()`` and BEFORE ``update_metric`` syncs on step N, so
        what it starts runs beside the device's work. ``Module`` places
        the batch on the device(s) there; a no-op here. It must not
        change what ``get_outputs()`` returns: step N's outputs are
        read after it."""

    def _flush_numerics(self):
        """Drain the bound executor's deferred numerics sentinel (the
        per-step verdict is read one step behind); no-op for modules
        without a fused-step executor."""
        exe = getattr(self, "_exec", None)
        if exe is not None and hasattr(exe, "flush_numerics"):
            exe.flush_numerics()

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        raise NotImplementedError()

    # -- binding -----------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()


class _BatchEndParam(object):
    """Callback parameter bundle (reference: model.py BatchEndParam)."""

    def __init__(self, epoch, nbatch, eval_metric, locals=None):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = eval_metric
        self.locals = locals
