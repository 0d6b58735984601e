"""Mean wall of one prefill (one admission): the delta of the
``decode/prefill_seconds`` histogram's sum over the delta of its count,
over the window. The mean and not a median: the histogram keeps buckets,
and its sum and count are exact."""

LAYER = "DecodeEngine prefill"
UNIT = "ms"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    c = run.samples["window_counts"]
    if not c["prefills"]:
        return None
    return c["prefill_seconds"] / c["prefills"] * 1e3
