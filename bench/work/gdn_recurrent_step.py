"""Operations and bytes the gated delta rule NEEDS for one token of one
sequence in one linear layer (``mxnet_tpu/ops/pallas/gated_delta.py:
gdn_recurrent_step``), whatever implements it: every value head's (dk, dv)
float32 state is read and written once — 2 x value heads x dk x dv x 4
bytes, 4.19 MB at 32 heads of 128 x 128 — beside the token's vectors (q and
k a key head, v a value head, the decay and the write strength in, the
read-out out, float32), against 7 operations a state element (the decay's
product, S^T k, the outer product and its sum, S^T q): 0.9 FLOP a byte, so
the step is bound by HBM and its roofline time is bytes over the HBM rate
(the operations over the bf16 peak are taken beside it for form: they are
the VPU's, and 200 times shorter). The convolution's tail is not the
kernel's: XLA gathers and scatters it outside."""

# The short name the device trace prints for the Mosaic kernel: a
# custom-call named after its jitted wrapper (``_gdn_recurrent.N``).
TRACE_NAME = r"^_gdn_recurrent(\.\d+)?$"


def flops(rows, value_heads, dk, dv):
    """``rows``: live sequences x linear layers (the spans'
    ``linear_rows``)."""
    return rows * 7 * value_heads * dk * dv


def nbytes(rows, key_heads, value_heads, dk, dv):
    state = 2 * value_heads * dk * dv * 4
    vectors = (2 * key_heads * dk + 2 * value_heads * dv
               + 2 * value_heads) * 4
    return rows * (state + vectors)


def roofline_seconds(rows, key_heads, value_heads, dk, dv, peaks):
    return max(nbytes(rows, key_heads, value_heads, dk, dv)
               / peaks["hbm_bytes_per_s"],
               flops(rows, value_heads, dk, dv) / peaks["bf16_flops_per_s"])
