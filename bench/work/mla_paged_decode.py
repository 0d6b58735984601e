"""Operations and bytes of ``mla_paged_decode`` (``mxnet_tpu/ops/pallas/
mla_attention.py``), the absorbed latent attention of a decode step: every
cached vector a live row attends (``latent_width`` = kv_lora_rank + rope
values) is read ONCE and serves as key and, in its first ``kv_rank``
columns, as value for all the heads: ``heads x 2 x (latent_width +
kv_rank)`` operations against ``latent_width x itemsize`` bytes — 242
FLOP/B at 128 heads of 576 / 512 in bf16, the v5e's ridge (197 T / 819 G =
240). So the kernel is bound by the MXU and by HBM at once, and its roofline
time is the LARGER of the two. This is the ABSORBED count whatever the
program does: a program that decompressed per head would do more work, and
that is not the kernel's need."""

# The short name the device trace prints for the Mosaic kernel: a
# custom-call named after its jitted wrapper (``_mla_paged_decode.N``).
TRACE_NAME = r"^_mla_paged_decode(\.\d+)?$"


def flops(latent_tokens, heads, latent_width, kv_rank):
    """``latent_tokens``: cached vectors attended, summed over the live
    rows and the layers (the spans' ``latent_context_tokens``)."""
    return latent_tokens * heads * 2 * (latent_width + kv_rank)


def nbytes(latent_tokens, calls_rows, heads, latent_width, kv_rank,
           itemsize):
    """``calls_rows``: rows x layers — a query (heads x latent_width) in
    and an output (heads x kv_rank) out for each."""
    return (latent_tokens * latent_width
            + calls_rows * heads * (latent_width + kv_rank)) * itemsize


def roofline_seconds(latent_tokens, calls_rows, heads, latent_width,
                     kv_rank, itemsize, peaks):
    return max(nbytes(latent_tokens, calls_rows, heads, latent_width,
                      kv_rank, itemsize) / peaks["hbm_bytes_per_s"],
               flops(latent_tokens, heads, latent_width, kv_rank)
               / peaks["bf16_flops_per_s"])
