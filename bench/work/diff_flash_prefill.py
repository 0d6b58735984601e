"""Operations ``diff_flash_prefill`` NEEDS for one prompt
(``mxnet_tpu/ops/pallas/diff_attention.py``): per differential head (half
the query heads) and (query, key) pair it may see, its two maps' scores (a
head_dim product each) and their weighted values over the pair's 2 x
head_dim value (two of them): 6 x head_dim multiply-adds, 12 x head_dim
operations — in the full layer over every causal pair, in a window layer
over the pairs within the window. Bound by the MXU: the roofline time is
operations over the bf16 peak (the kernel's zero halves and float32
operands are not the need)."""

# The short name the device trace prints for the Mosaic kernel: a
# custom-call named after its jitted wrapper (``_diff_flash_prefill.N``).
TRACE_NAME = r"^_diff_flash_prefill(\.\d+)?$"


def pairs(length, window=None):
    """(query, key) pairs a causal layer sees over a prompt of ``length``
    tokens, within ``window`` where given."""
    if window is None or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def flops(length, full_layers, window_layers, window, n_heads, head_dim):
    return 12 * head_dim * (n_heads // 2) * (
        full_layers * pairs(length) + window_layers * pairs(length, window))


def roofline_seconds(total_flops, peaks):
    return total_flops / peaks["bf16_flops_per_s"]
