"""Training-fit plumbing for the image-classification CLIs.

Reference analog: example/image-classification/common/fit.py:83-90 —
network/kv-store flag wiring into Module.fit with lr scheduling,
checkpoint callbacks, and Speedometer logging. TPU-native notes:
``--tpus 0,1,...`` (alias ``--gpus``) builds a data-parallel context
list (one mesh-sharded program, see mxnet_tpu/module/module.py
_install_dp_mesh); ``--kv-store dist_tpu_sync`` selects the allreduce
distributed mode.
"""
from __future__ import annotations

import logging
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
import mxnet_tpu as mx  # noqa: E402


def get_epoch_size(args, kv):
    nworker = kv.num_workers if kv else 1
    return math.ceil(int(args.num_examples / nworker) / args.batch_size)


def _get_lr_scheduler(args, kv):
    if not getattr(args, "lr_factor", None) or args.lr_factor >= 1:
        return (args.lr, None)
    epoch_size = get_epoch_size(args, kv)
    begin_epoch = args.load_epoch or 0
    step_epochs = [int(l) for l in args.lr_step_epochs.split(",")]
    lr = args.lr
    for s in step_epochs:
        if begin_epoch >= s:
            lr *= args.lr_factor
    if lr != args.lr:
        logging.info("Adjusted learning rate to %e for epoch %d",
                     lr, begin_epoch)
    steps = [epoch_size * (x - begin_epoch)
             for x in step_epochs if x - begin_epoch > 0]
    if not steps:
        return (lr, None)
    return (lr, mx.lr_scheduler.MultiFactorScheduler(
        step=steps, factor=args.lr_factor, base_lr=args.lr))


def _load_model(args, rank=0):
    if getattr(args, "load_epoch", None) is None:
        return (None, None, None)
    assert args.model_prefix is not None
    return mx.model.load_checkpoint(args.model_prefix, args.load_epoch)


def _save_model(args, rank=0):
    if args.model_prefix is None:
        return None
    dst_dir = os.path.dirname(args.model_prefix)
    if dst_dir and not os.path.isdir(dst_dir):
        os.makedirs(dst_dir)
    prefix = args.model_prefix if rank == 0 else "%s-%d" % (
        args.model_prefix, rank)
    return mx.callback.do_checkpoint(prefix, period=args.save_period)


def add_fit_args(parser):
    train = parser.add_argument_group("Training")
    train.add_argument("--network", type=str, default="resnet",
                       help="the network to train")
    train.add_argument("--num-layers", type=int, default=50)
    train.add_argument("--tpus", "--gpus", dest="tpus", type=str,
                       default=None,
                       help="comma list of device ids for data parallelism, "
                            "e.g. 0,1,2,3; empty means one device")
    train.add_argument("--kv-store", type=str, default="device",
                       help="local | device | dist_tpu_sync | dist_sync | "
                            "dist_async")
    train.add_argument("--num-epochs", type=int, default=90)
    train.add_argument("--lr", type=float, default=0.1)
    train.add_argument("--lr-factor", type=float, default=0.1)
    train.add_argument("--lr-step-epochs", type=str, default="30,60,80")
    train.add_argument("--optimizer", type=str, default="sgd")
    train.add_argument("--mom", type=float, default=0.9)
    train.add_argument("--wd", type=float, default=1e-4)
    train.add_argument("--batch-size", type=int, default=128)
    train.add_argument("--disp-batches", type=int, default=20)
    train.add_argument("--model-prefix", type=str, default=None)
    train.add_argument("--save-period", type=int, default=1)
    train.add_argument("--load-epoch", type=int, default=None)
    train.add_argument("--max-batches", type=int, default=None,
                       help="stop every epoch after this many batches "
                            "(smoke tests / benchmarking)")
    train.add_argument("--monitor", type=int, default=0)
    return train


def fit(args, network, data_loader, batch_end_callback=None):
    """Train ``network`` with the flags in ``args``
    (reference: common/fit.py fit). ``batch_end_callback``: extra
    callback(s) run after the Speedometer."""
    kv = None
    if "dist" in args.kv_store:
        kv = mx.kvstore.create(args.kv_store)
    head = "%(asctime)-15s Node[" + str(kv.rank if kv else 0) + "] %(message)s"
    logging.basicConfig(level=logging.INFO, format=head)
    logging.info("start with arguments %s", args)

    epoch_size = get_epoch_size(args, kv)
    train, val = data_loader(args, kv)

    if args.tpus:
        devs = [mx.tpu(int(i)) for i in args.tpus.split(",")]
    else:
        # raises when the machine has no accelerator, unless the
        # platform was forced with JAX_PLATFORMS=cpu (mx.tpu semantics)
        devs = mx.tpu(0)

    lr, lr_scheduler = _get_lr_scheduler(args, kv)
    sym, arg_params, aux_params = _load_model(args, kv.rank if kv else 0)
    if sym is None:
        sym = network

    mod = mx.module.Module(symbol=sym, context=devs)

    optimizer_params = {
        "learning_rate": lr,
        "wd": args.wd,
        "lr_scheduler": lr_scheduler,
    }
    if args.optimizer in ("sgd", "nag", "signum"):
        optimizer_params["momentum"] = args.mom

    checkpoint = _save_model(args, kv.rank if kv else 0)
    batch_end_cbs = [mx.callback.Speedometer(args.batch_size,
                                             args.disp_batches)]
    if batch_end_callback is not None:
        batch_end_cbs += (batch_end_callback
                          if isinstance(batch_end_callback, list)
                          else [batch_end_callback])

    eval_metrics = ["accuracy"]
    if args.num_classes >= 5:
        eval_metrics.append(mx.metric.create("top_k_accuracy", top_k=5))

    monitor = mx.monitor.Monitor(1, pattern=".*") if args.monitor else None

    if args.max_batches:
        train = mx.io.ResizeIter(train, args.max_batches)

    mod.fit(train,
            begin_epoch=args.load_epoch or 0,
            num_epoch=args.num_epochs,
            eval_data=val,
            eval_metric=eval_metrics,
            kvstore=kv if kv else args.kv_store,
            optimizer=args.optimizer,
            optimizer_params=optimizer_params,
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            arg_params=arg_params,
            aux_params=aux_params,
            batch_end_callback=batch_end_cbs,
            epoch_end_callback=checkpoint,
            allow_missing=True,
            monitor=monitor)
    return mod
