"""Bytes ``diff_paged_decode`` reads for one decode step
(``mxnet_tpu/ops/pallas/diff_attention.py``): every LIVE page of every
row's table, keys and values — a page is page_size tokens of kv_heads / 2
pairs of 2 x head_dim, one read gives both maps of every differential
head — in each layer that reads it: the one full layer's pages by each of
its readers (that layer and every cross-attention layer: 8 reads of the
same pages at the served size, counted as 8), a window layer's live ring
pages once each; plus every row's query rows and outputs. Bound by HBM: the
roofline time is bytes over the HBM rate."""

# The short name the device trace prints for the Mosaic kernel: a
# custom-call named after its jitted wrapper (``_diff_paged_decode.N``).
TRACE_NAME = r"^_diff_paged_decode(\.\d+)?$"


def page_bytes(page_size, kv_heads, head_dim, itemsize):
    """A page's keys AND values."""
    return 2 * page_size * kv_heads * head_dim * itemsize


def step_bytes(full_pages, readers, window_pages, window_layers, rows,
               page_size, n_heads, kv_heads, head_dim, itemsize):
    """``full_pages``: live pages of the full layer over a step's rows (the
    spans' ``full_pages_read``); ``window_pages``: live ring pages of a
    window layer over them (``window_pages_read``); ``rows``: live rows.
    A row's query rows and output are 2 n_heads x 2 head_dim each way, a
    layer."""
    pages = (full_pages * readers + window_pages * window_layers) \
        * page_bytes(page_size, kv_heads, head_dim, itemsize)
    qo = 2 * rows * (readers + window_layers) * n_heads * 2 * head_dim \
        * itemsize
    return pages + qo


def roofline_seconds(nbytes, peaks):
    return nbytes / peaks["hbm_bytes_per_s"]
