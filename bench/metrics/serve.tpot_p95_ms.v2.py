"""95th percentile, over the requests due in the window, of ``(t_done -
t_first) / (tokens - 1)``: what ``serve_tpot_p95_ms`` is in the first
serving cell, RECORDED here and not bounded. Over the 43 requests of this
cell's window it is the second or third worst one, decided by which short
answers a blocking 0.5-1.1 s prefill of a long prompt lands on: two sets of
six seeds spread it by 17.3 and 19.8 % against a bound of 6 % (PERF.md
sections 2 and 7), so the cell is not judged by it until a ``benchmark``
issue gives it a statistic or a bound of its own."""
from bench import stats

LAYER = "DecodeEngine step"
UNIT = "ms"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    return stats.percentile(
        [v for v in (stats.tpot_ms(r) for r in run.samples["requests"])
         if v is not None], 95)
