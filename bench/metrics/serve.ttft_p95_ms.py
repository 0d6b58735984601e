"""95th percentile, over the requests due in the window, of first token
time minus the time the request was DUE (open loop; a failed request counts
as missing, ``bench/stats.py``): the tail of what the end-to-end metric
``serve_ttft_mean_ms`` takes the mean of. The tail is recorded here and
not bounded: over the ~90 requests of a window its runs spread by 5-11 %
(two sets of six, PR 23), which asks for a bound five times that, and the
contract allows 10 %. It spans the whole way of a request to its first
token - the generator's send, the wait for a slot, the prefill - and so
has that way as its layer, not one stage of it."""
from bench import stats

LAYER = "DecodeEngine request path"
UNIT = "ms"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    return stats.percentile(
        [stats.ttft_ms(r) for r in run.samples["requests"]], 95)
