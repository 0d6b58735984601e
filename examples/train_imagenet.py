#!/usr/bin/env python
"""Train image-classification networks on ImageNet-format RecordIO data —
the north-star CLI (reference: example/image-classification/
train_imagenet.py:38-40 + common/fit.py:83-90).

    # real data (one chip):
    python examples/train_imagenet.py --network resnet --num-layers 50 \
        --data-train train.rec --batch-size 32

    # synthetic-data benchmark over 4 devices, allreduce kvstore:
    python examples/train_imagenet.py --network resnet --benchmark 1 \
        --tpus 0,1,2,3 --kv-store device --batch-size 128 --max-batches 50

    # multi-host: launch one process per host under tools/launch.py with
    # --kv-store dist_tpu_sync; data shards via num_parts/part_index.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import mxnet_tpu as mx  # noqa: E402
from common import data, fit  # noqa: E402


def set_imagenet_aug(parser):
    """Standard ImageNet training augmentation defaults."""
    parser.set_defaults(rgb_mean="123.68,116.779,103.939",
                        rgb_std="58.393,57.12,57.375",
                        random_crop=0, random_resized_crop=1,
                        random_mirror=1, min_random_area=0.08,
                        max_random_aspect_ratio=4. / 3.,
                        min_random_aspect_ratio=3. / 4.,
                        brightness=0.4, contrast=0.4, saturation=0.4,
                        pca_noise=0.1)


def get_network(args):
    from mxnet_tpu import models
    image_shape = tuple(int(x) for x in args.image_shape.split(","))
    name = args.network
    if name == "resnet":
        return models.resnet(num_classes=args.num_classes,
                             num_layers=args.num_layers,
                             image_shape=image_shape)
    if name == "alexnet":
        return models.alexnet(num_classes=args.num_classes)
    if name == "vgg":
        return models.vgg(num_classes=args.num_classes,
                          num_layers=args.num_layers)
    if name == "mobilenet":
        return models.mobilenet(num_classes=args.num_classes)
    if name == "mlp":
        return models.mlp(num_classes=args.num_classes)
    if name in ("inception-bn", "inception_bn"):
        return models.inception_bn(num_classes=args.num_classes)
    raise ValueError("unknown --network %r (choose from resnet, alexnet, "
                     "vgg, mobilenet, mlp, inception-bn)" % name)


def main(argv=None, batch_end_callback=None):
    """Train with the flags in ``argv`` (default ``sys.argv``); returns
    the fitted Module. ``batch_end_callback`` runs after the CLI's own
    Speedometer (chip_smoke.py watches the loss through it)."""
    parser = argparse.ArgumentParser(
        description="train imagenet-1k",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    fit.add_fit_args(parser)
    data.add_data_args(parser)
    data.add_data_aug_args(parser)
    parser.set_defaults(network="resnet", num_layers=50, num_classes=1000,
                        num_examples=1281167, image_shape="3,224,224",
                        batch_size=32, lr=0.1, lr_step_epochs="30,60,80")
    args = parser.parse_args(argv)
    net = get_network(args)
    return fit.fit(args, net, data.get_rec_iter,
                   batch_end_callback=batch_end_callback)


if __name__ == "__main__":
    main()
