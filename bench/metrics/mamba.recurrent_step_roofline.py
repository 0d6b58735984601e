"""``mamba_recurrent_step``'s share of its roofline over the traced part of
the window: for every logged ``decode.step`` span that ended there, the
time its ``mamba_rows`` (REAL rows x Mamba layers: dummy slots are no
work) need at the HBM rate — a row's state of one layer read and written,
and its vectors (``bench/work/mamba_recurrent_step.py``) — summed, over
the kernel's measured time (its own events on device 0 there)."""
from bench import span_log

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)


def read(run):
    window = run.samples["trace_host_window"]
    if run.trace is None or run.peaks is None or not window:
        return None
    kernel = run.work("mamba_recurrent_step")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    m = run.config["model"]
    rows = sum(r["attrs"]["mamba_rows"] for r in span_log.records()
               if r["name"] == "decode.step"
               and "mamba_rows" in r["attrs"] and lo <= r["t1"] < hi)
    if not rows:
        return None
    return 100.0 * kernel.roofline_seconds(
        rows, m["mamba_d_state"], m["mamba_expand"] * m["d_model"],
        run.peaks) / seconds
