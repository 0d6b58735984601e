"""95th percentile of the host clock between two ``batch_end_callback``s,
over the window's clean steps (not the ones the profiler ran in).

Valid while ``update_metric``'s fetch of the outputs makes every callback a
device sync, as it does today; when ROADMAP A4 takes that fetch off the
step, this becomes an enqueue time and has to be re-read from the trace.
"""
from bench import stats

LAYER = "fit loop"
UNIT = "ms"
MOVES = "train_samples_per_s"
DRIVERS = ("fit_cli",)


def read(run):
    walls = [s["wall"] * 1e3 for s in run.samples["steps"]
             if s["phase"] == "window"]
    return stats.percentile(walls, 95)
