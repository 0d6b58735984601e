"""How late the benchmark's own generator sent: 95th percentile of send
time minus due time over the requests due in the window. A starved
generator must not be read as a fast server."""
from bench import stats

LAYER = "benchmark load generator"
UNIT = "ms"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    return stats.percentile(
        [stats.lateness_ms(r) for r in run.samples["requests"]
         if r.sent is not None], 95)
