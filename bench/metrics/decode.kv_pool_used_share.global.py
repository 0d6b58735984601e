"""Mean share of a page pool that was reserved, as each arrival of the
window found it: 1 - the engine's ``decode/pages_free{kind}`` gauge over the
pool's capacity, read by the driver right after every ``submit`` due in the
window — here the GLOBAL layers' pool (``.window`` reads the other with
this file's ``read_kind``). A request reserves its whole life's pages at
admission (of the window pool at most a ring), queued requests included; a
pool near 100 % refuses (``PagePoolExhausted`` names the kind)."""
from bench import stats

LAYER = "DecodeEngine scheduler"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read_kind(run, kind):
    used = run.samples.get("pages_used", {}).get(kind)
    if not used:
        return None
    return 100.0 * stats.mean(used)


def read(run):
    return read_kind(run, "global")
