"""Operations and bytes of ``moe_grouped_ffn`` (``mxnet_tpu/ops/pallas/
moe_ffn.py``) for one call of the model: the gated MLP of every
(token, expert) assignment, and the weights of every expert that received
a row. A decode step (a few rows an expert) is bound by the weights'
bytes, a long prefill (hundreds of rows an expert) by the MXU: the
roofline time of a call is the larger of the two."""

# The short name the device trace prints for the Mosaic kernel: a
# custom-call named after its jitted wrapper (``_moe_grouped_ffn.N``).
TRACE_NAME = r"^_moe_grouped_ffn(\.\d+)?$"


def flops(rows, d_model, d_ff):
    """``rows``: assignments computed (tokens x experts a token), summed
    over the call's layers. Three products a row: gate, up, down."""
    return 2 * 3 * rows * d_model * d_ff


def nbytes(rows, active_experts, d_model, d_ff, itemsize):
    """``active_experts``: experts that received a row, summed over the
    call's layers — each one's three maps are read once; every row is
    read and written once."""
    weights = active_experts * 3 * d_model * d_ff * itemsize
    return weights + 2 * rows * d_model * itemsize


def roofline_seconds(rows, active_experts, d_model, d_ff, itemsize, peaks):
    return max(nbytes(rows, active_experts, d_model, d_ff, itemsize)
               / peaks["hbm_bytes_per_s"],
               flops(rows, d_model, d_ff) / peaks["bf16_flops_per_s"])
