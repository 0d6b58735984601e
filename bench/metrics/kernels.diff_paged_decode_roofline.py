"""``diff_paged_decode``'s share of its roofline over the traced part of
the window: every logged ``decode.step`` span that ended there carries the
live pages its attends walk — ``full_pages_read`` of the one full layer,
read by each of its ``shared_kv_readers`` (counted that many times), and
``window_pages_read`` of a window layer's ring — and its live rows; the
bytes are those pages' keys and values plus the rows' queries and outputs
(``bench/work/diff_paged_decode.py``), over the HBM rate, over the
kernel's measured time. Bound: HBM."""
from bench import span_log

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)


def read(run):
    window = run.samples["trace_host_window"]
    if run.trace is None or run.peaks is None or not window:
        return None
    kernel = run.work("diff_paged_decode")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    m = run.config["model"]
    n_window = sum(1 for li in range(m["cross_from"])
                   if not m["mamba_layout"][li] and m["window_layout"][li])
    nbytes = 0
    for r in span_log.records():
        attrs = r["attrs"]
        if (r["name"] == "decode.step" and "full_pages_read" in attrs
                and lo <= r["t1"] < hi):
            nbytes += kernel.step_bytes(
                attrs["full_pages_read"], attrs["shared_kv_readers"],
                attrs.get("window_pages_read", 0), n_window,
                attrs["state_rows"], run.config["engine"]["page_size"],
                m["n_heads"], m["n_kv_heads"], m["head_dim"],
                run.samples["kv_itemsize"])
    if not nbytes:
        return None
    return 100.0 * kernel.roofline_seconds(nbytes, run.peaks) / seconds
