"""Bytes ``paged_decode_attention`` needs for one decode step of one layer:
for every live sequence the keys and values of its context (what the block
table's pages hold up to its length), plus its query and its output. One
multiply-accumulate per key element and per value element against some
bytes each: the kernel is bound by HBM, so its roofline time is bytes over
the HBM rate."""

# The short name the device trace prints for the Mosaic kernel: a
# custom-call named after its jitted wrapper (``_paged_decode.N``).
TRACE_NAME = r"^_paged_decode(\.\d+)?$"


def bytes_per_layer_step(context_lengths, kv_heads, n_heads, head_dim,
                         itemsize):
    """``context_lengths``: tokens each live sequence attends to."""
    kv = 2 * sum(context_lengths) * kv_heads * head_dim * itemsize
    qo = 2 * len(context_lengths) * n_heads * head_dim * itemsize
    return kv + qo


def flops_per_layer_step(context_lengths, n_heads, head_dim):
    return 2 * 2 * sum(context_lengths) * n_heads * head_dim


def roofline_seconds(nbytes, peaks):
    return nbytes / peaks["hbm_bytes_per_s"]
