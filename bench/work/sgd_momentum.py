"""Bytes the SGD-momentum rule needs per step: for every float32 parameter
it reads the weight, the gradient and the momentum and writes the weight
and the momentum — 5 x 4 = 20 bytes a parameter. The rule is bound by HBM
(4 FLOP against 20 bytes), so its roofline time is bytes over the HBM
rate."""

# The short name the device trace prints for ops/pallas/fused_update.py's
# kernel (a custom-call named after its jitted wrapper, ``_fused_update.N``)
# and the name under which other ops read its results.
TRACE_NAME = r"^_fused_update(\.\d+)?$"
RESULT_NAME = r"^pallas_call(\.\d+)?$"


def bytes_per_step(parameters, itemsize=4):
    return 5 * itemsize * parameters


def roofline_seconds(parameters, peaks, itemsize=4):
    return bytes_per_step(parameters, itemsize) / peaks["hbm_bytes_per_s"]
