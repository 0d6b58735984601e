"""``decode.steps_ahead_share`` and its ``.v2`` twin on made-up span logs:
the share of the engine's ``decode.step`` spans that went ahead, counted
over the steps that ended in the traced part, and ``None`` where a run was
not traced or its program's steps carry no ``ahead``. CPU only; the
program's log is replaced by the made-up one."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_bench_span_log import ORIGIN, decode_run, metric, rec  # noqa: E402


def ahead_log():
    """A pipelined engine's steps at 10.0, 10.01, ... s: each overlaps the
    next (dispatched before the one before it landed) and is logged under
    the pass that dispatched it; a request's copy of a step carries no
    ``ahead``. Steps 0 and 5 did not go ahead (a prefill came before)."""
    log = []
    for n in range(10):
        at = 10.0 + n * 0.01
        attrs = {"context_tokens": 100 + n, "ahead": n not in (0, 5)}
        if not attrs["ahead"]:
            attrs["drained_for"] = "admit"
        log.append(rec("decode.step", at, at + 0.014, "s%d" % n, "it%d" % n,
                       attrs))
        log.append(rec("decode.iteration", at, at + 0.01, "it%d" % n, None,
                       {"live": 1}))
        log.append(rec("decode.step", at, at + 0.014, "req%d" % n, "http",
                       {"slots": 1, "bucket": 1, "live_pages": 4}))
    return log


@pytest.mark.parametrize("suffix", ["", ".v2"])
def test_steps_ahead_share_counts_the_engines_steps_of_the_traced_part(
        suffix, monkeypatch):
    read = metric("decode.steps_ahead_share" + suffix).read
    run = decode_run(ahead_log(), monkeypatch)
    assert read(run) == pytest.approx(80.0)
    # only the steps that ENDED inside the traced part: 10.044 .. 10.094 s
    run = decode_run(ahead_log(), monkeypatch,
                     host_window=(ORIGIN + 10.04, ORIGIN + 10.1))
    assert read(run) == pytest.approx(100.0 * 5 / 6)


@pytest.mark.parametrize("suffix", ["", ".v2"])
def test_steps_ahead_share_is_silent_where_there_is_nothing_to_read(
        suffix, monkeypatch):
    read = metric("decode.steps_ahead_share" + suffix).read
    assert read(decode_run(ahead_log(), monkeypatch, traced=False)) is None
    # a program whose steps carry no ``ahead``, as before the pipelined loop
    old = [dict(r, attrs=dict((k, v) for k, v in r["attrs"].items()
                              if k not in ("ahead", "drained_for")))
           for r in ahead_log()]
    assert read(decode_run(old, monkeypatch)) is None
