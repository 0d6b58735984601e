"""Operations and bytes the gated delta rule NEEDS for one prompt token in
one linear layer (``mxnet_tpu/ops/pallas/gated_delta.py:
gdn_chunk_prefill``), counted from the RECURRENCE and not from the chunked
form that computes it: the same 7 operations a state element as a decode
step's token (``bench/work/gdn_recurrent_step.py``) — the state itself
stays on the chip between a prompt's tokens, so it costs no bytes — and the
token's vectors in and out (q and k a key head, v and the read-out a value
head in the served type, the decay and the write strength float32). A
count that grew with the chunk the kernel picked (the WY form's (chunk,
chunk) products and its inverse) would reward a larger chunk for doing more
work. At 32 heads of 128 x 128 in bf16: 3.67 MFLOP against 24.8 KB a token
a layer, 148 FLOP/B — under the v5e's ridge of 240, so by its NEED the
prefill's rule is bound by HBM, narrowly; the roofline time is the larger
of the two."""

# The short name the device trace prints for the Mosaic kernel: a
# custom-call named after its jitted wrapper (``_gdn_chunk.N``).
TRACE_NAME = r"^_gdn_chunk(\.\d+)?$"


def flops(tokens, value_heads, dk, dv):
    """``tokens``: real prompt tokens x linear layers (the spans'
    ``linear_tokens``; a bucket's padding is no work)."""
    return tokens * 7 * value_heads * dk * dv


def nbytes(tokens, key_heads, value_heads, dk, dv, itemsize):
    return tokens * ((2 * key_heads * dk + 2 * value_heads * dv) * itemsize
                     + 2 * value_heads * 4)


def roofline_seconds(tokens, key_heads, value_heads, dk, dv, itemsize,
                     peaks):
    return max(nbytes(tokens, key_heads, value_heads, dk, dv, itemsize)
               / peaks["hbm_bytes_per_s"],
               flops(tokens, value_heads, dk, dv)
               / peaks["bf16_flops_per_s"])
