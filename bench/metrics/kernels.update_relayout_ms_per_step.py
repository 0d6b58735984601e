"""Device time per training step of the ops that only carry data into and
out of the fused-update kernels: ``fused_update.py:_flat`` flattens, pads
and reshapes every parameter, gradient and momentum to (rows, 128) before
its kernel and back after it, and on the chip each of those is a physical
relayout of the array, not a bitcast. Found by dataflow in the trace's own
HLO text, not by op order or shape: an op counts when a fused-update
custom-call reads its result, or when it reads a kernel's result
(``bench/work/sgd_momentum.py`` ``TRACE_NAME``/``RESULT_NAME``), and it is a
reshape, pad, slice, copy or bitcast (a gradient's producing fusion is not
the update's cost)."""
import re

from bench import trace_reduce

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
DRIVERS = ("fit_cli",)
STEP = "bench.fit_step"
CARRIER = re.compile(r"^(reshape|pad|slice|copy|bitcast|transpose)")


def read(run):
    if run.trace is None:
        return None
    rule = run.work("sgd_momentum")
    steps = run.trace.count(STEP)
    kernel, result = re.compile(rule.TRACE_NAME), re.compile(rule.RESULT_NAME)
    text = run.trace.op_text
    feeds = set()
    for name in text:
        if kernel.search(name):
            feeds.update(trace_reduce.operands(text[name]))
    if not steps or not feeds:
        return None
    seconds = 0.0
    for name, own in run.trace.op_seconds.items():
        if not CARRIER.match(name):
            continue
        if name in feeds or any(result.search(o)
                                for o in trace_reduce.operands(text[name])):
            seconds += own
    return seconds / steps * 1e3
