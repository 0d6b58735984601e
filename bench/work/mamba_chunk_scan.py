"""Operations and bytes Mamba's selective scan NEEDS for one prompt token
in one Mamba layer (``mxnet_tpu/ops/pallas/mamba.py:mamba_chunk_scan``),
counted from the RECURRENCE: the same 7 operations a state element as a
decode step's token (``bench/work/mamba_recurrent_step.py``) — the state
stays on the chip between a prompt's tokens and costs no bytes — and the
token's vectors in and out: its input and its read-out a channel in the
served type, its step size a channel and B and C a state in float32. At
5120 channels of 16 states in bf16: 0.57 MFLOP against 41.1 KB a token a
layer, 14 FLOP/B — far under the v5e's ridge of 240, so by its NEED the
prefill's scan is bound by HBM and the roofline time is the bytes' (the
larger of the two is taken). The scan runs on the VPU token after token,
which neither rate describes: the share reads how far the kernel is from
the bytes it must move."""

# The short name the device trace prints for the Mosaic kernel: a
# custom-call named after its jitted wrapper (``_mamba_scan.N``).
TRACE_NAME = r"^_mamba_scan(\.\d+)?$"


def flops(tokens, d_state, channels):
    """``tokens``: real prompt tokens x Mamba layers (the spans'
    ``mamba_tokens``; a bucket's padding is no work)."""
    return tokens * 7 * d_state * channels


def nbytes(tokens, d_state, channels, itemsize):
    return tokens * (channels * (2 * itemsize + 4) + 2 * d_state * 4)


def roofline_seconds(tokens, d_state, channels, itemsize, peaks):
    return max(nbytes(tokens, d_state, channels, itemsize)
               / peaks["hbm_bytes_per_s"],
               flops(tokens, d_state, channels)
               / peaks["bf16_flops_per_s"])
