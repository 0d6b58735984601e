"""Percentiles and open-loop arithmetic, kept with the benchmark so that no
later PR can change how a tail is taken."""
import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    the closest ranks (numpy's default). ``None`` for no values."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    k = (len(vals) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (k - lo))


def mean(values):
    """The arithmetic mean; ``None`` for no values."""
    vals = list(values)
    return float(sum(vals)) / len(vals) if vals else None


def spread(values):
    """The distance between the first and third quartile as a share of the
    median, the quartiles as ``statistics.quantiles(values, n=4)`` gives
    them — the spread the bounds are set from."""
    import statistics
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


class Request(object):
    """One request of an open loop, on one clock (seconds).

    ``due``: when the schedule says it is sent; ``sent``: when the
    generator sent it; ``first``/``done``: first and last token (``None``
    for a request that was refused, failed or timed out); ``enq``/``admit``:
    the server's stamps for queued and given a slot; ``tokens``:
    output tokens received; ``gave_up``: when the benchmark stopped
    waiting for a request that never finished."""

    __slots__ = ("due", "sent", "first", "done", "tokens", "error",
                 "gave_up", "prompt_len", "want_tokens", "enq", "admit")

    def __init__(self, due, sent=None, first=None, done=None, tokens=0,
                 error=None, gave_up=None, prompt_len=0, want_tokens=0,
                 enq=None, admit=None):
        self.due, self.sent, self.first, self.done = due, sent, first, done
        self.tokens, self.error, self.gave_up = tokens, error, gave_up
        self.prompt_len, self.want_tokens = prompt_len, want_tokens
        self.enq, self.admit = enq, admit   # the server's own stamps

    @property
    def failed(self):
        return (self.error is not None or self.done is None
                or self.tokens < self.want_tokens)


def ttft_ms(req):
    """First token minus the time the request was DUE (not sent), so a
    stall of the generator or the server counts against every request
    behind it. A failed request counts as missing any limit: it is given
    the time until the benchmark gave up on it, which is a floor of what
    its user saw and always among the worst."""
    if req.first is None:
        return (req.gave_up - req.due) * 1e3
    return (req.first - req.due) * 1e3


def tpot_ms(req):
    """Time per output token after the first; ``None`` for a request with
    fewer than two tokens (it has no gap)."""
    if req.first is None or req.done is None or req.tokens < 2:
        return None
    return (req.done - req.first) / (req.tokens - 1) * 1e3


def lateness_ms(req):
    """How late the generator sent it."""
    return (req.sent - req.due) * 1e3 if req.sent is not None else None
