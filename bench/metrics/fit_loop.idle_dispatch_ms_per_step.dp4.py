"""``fit_loop.idle_dispatch_ms_per_step`` in the four-chip cell, whose
rate is an end-to-end metric of its own (``train_samples_per_s.dp4``): the
same reader, another ``MOVES``."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "fit_loop.idle_dispatch_ms_per_step.py"))
LAYER, UNIT, DRIVERS, read = _of.LAYER, _of.UNIT, _of.DRIVERS, _of.read
MOVES = "train_samples_per_s.dp4"
