"""``gdn_recurrent_step``'s share of its roofline over the traced part of
the window: for every logged ``decode.step`` span that ended there, the
time its ``linear_rows`` (REAL rows x linear layers: dummy slots are no
work) need at the HBM rate — a row's states of one layer read and written,
and its vectors (``bench/work/gdn_recurrent_step.py``) — summed, over the
kernel's measured time (its own events on device 0 there, no async copy
beside them)."""
from bench import span_log

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    window = run.samples["trace_host_window"]
    if run.trace is None or run.peaks is None or not window:
        return None
    kernel = run.work("gdn_recurrent_step")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    m = run.config["model"]
    rows = sum(r["attrs"]["linear_rows"] for r in span_log.records()
               if r["name"] == "decode.step"
               and "linear_rows" in r["attrs"] and lo <= r["t1"] < hi)
    if not rows:
        return None
    return 100.0 * kernel.roofline_seconds(
        rows, m["linear_key_heads"], m["linear_value_heads"],
        m["linear_key_dim"], m["linear_value_dim"], run.peaks) / seconds
