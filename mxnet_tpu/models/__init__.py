"""Symbolic model builders (reference: example/image-classification/symbols/).

These mirror the reference's benchmark topologies so the benchmarks measure
the same workloads as docs/faq/perf.md. The Gluon model zoo
(`mxnet_tpu.gluon.model_zoo`) is the imperative counterpart.
"""
from .resnet import get_symbol as resnet
from .mlp import get_symbol as mlp
from .alexnet import get_symbol as alexnet
from .vgg import get_symbol as vgg
from .mobilenet import get_symbol as mobilenet
from .inception_bn import get_symbol as inception_bn
