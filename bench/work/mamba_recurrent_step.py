"""Operations and bytes Mamba's selective scan NEEDS for one token of one
sequence in one Mamba layer (``mxnet_tpu/ops/pallas/mamba.py:
mamba_recurrent_step``), whatever implements it: the (d_state, channels)
float32 state is read and written once — 2 x 16 x 5120 x 4 bytes, 655 KB
at the served size — beside the token's vectors (its input and step size a
channel, B and C a state, the read-out a channel, float32), against 7
operations a state element (the decay's product and exponential, its
product with the state, the input's outer product and its sum, the
read-out's product and sum): 0.9 FLOP a byte, so the step is bound by HBM
and its roofline time is bytes over the HBM rate (the operations over the
bf16 peak are taken beside it for form). The convolution's tail is not the
kernel's: XLA gathers and scatters it outside."""

# The short name the device trace prints for the Mosaic kernel: a
# custom-call named after its jitted wrapper (``_mamba_step.N``).
TRACE_NAME = r"^_mamba_step(\.\d+)?$"


def flops(rows, d_state, channels):
    """``rows``: live sequences x Mamba layers (the spans'
    ``mamba_rows``)."""
    return rows * 7 * d_state * channels


def nbytes(rows, d_state, channels):
    return rows * (2 * d_state * channels * 4
                   + (3 * channels + 2 * d_state) * 4)


def roofline_seconds(rows, d_state, channels, peaks):
    return max(nbytes(rows, d_state, channels) / peaks["hbm_bytes_per_s"],
               flops(rows, d_state, channels) / peaks["bf16_flops_per_s"])
