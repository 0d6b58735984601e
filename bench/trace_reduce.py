"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, time per device op by name, collective time and its exposed part,
and the longest idle gaps with what the host was doing.

``load`` turns the file into plain ``Event`` tuples with nothing but JAX;
``summarize`` is pure arithmetic over them, checked in
``tests/zbench/test_bench_trace_reduce.py`` on made-up events and on the
small trace recorded on the chip beside this file
(``bench/testdata/``).

What a v5e trace looks like (looked at by hand, PR 23): one plane
``/device:TPU:<i>`` per chip whose line ``XLA Ops`` holds one event per
executed HLO op, named by the op's whole HLO text (``%fusion.17 =
f32[128,256,56,56]{...} fusion(...)``; a Pallas kernel is a ``custom-call``
named after its jitted wrapper, ``%_fused_update.3``; its results are read
as ``%pallas_call.N``); ``Async XLA Ops`` holds the copy-start/copy-done
pairs again, ``XLA Modules`` and ``Steps`` one event per program run. Host
threads are lines of ``/host:CPU``; the line ``python`` holds the
``TraceAnnotation`` spans the benchmark opens (names starting with
``bench.``). An ``Event``'s ``name`` is the op's short name (``fusion.17``),
its ``text`` the whole HLO text.
"""
import collections
import glob
import os
import re

Event = collections.namedtuple("Event", "plane line name start dur text")
Event.__new__.__defaults__ = ("",)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
WINDOW_ANNOTATION = "bench.window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")


def find_xplane(trace_dir):
    """The one ``.xplane.pb`` under a ``jax.profiler.start_trace``
    directory."""
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path):
    """Events of the device op lines and of the benchmark's host
    annotations; seconds on the trace's own clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(ANNOTATION_PREFIX):
                    continue
                events.append(Event(plane.name, line.name,
                                    short_name(ev.name), ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9, ev.name))
    return events


def short_name(text):
    """``%fusion.17 = f32[...] fusion(...)`` -> ``fusion.17``."""
    return text.split(" = ", 1)[0].lstrip("%") if " = " in text else text


def operands(text):
    """The ``%names`` an op's HLO text reads (all after its own)."""
    return re.findall(r"%([A-Za-z0-9_.\-]+)", text.split(" = ", 1)[-1])


def union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(events):
    """Per event, its duration minus what events nested inside it cover
    (a ``while`` or a call op holds its body's ops), so that times by name
    add up to the busy time."""
    out = []
    stack = []                      # [event, end, covered]
    for ev in sorted(events, key=lambda e: (e.start, -e.dur)):
        end = ev.start + ev.dur
        while stack and stack[-1][1] <= ev.start:
            done = stack.pop()
            out.append((done[0], done[0].dur - done[2]))
        if stack:
            stack[-1][2] += min(end, stack[-1][1]) - ev.start
        stack.append([ev, end, 0.0])
    while stack:
        done = stack.pop()
        out.append((done[0], done[0].dur - done[2]))
    return out


class Summary(object):
    """The reduction of one traced window."""

    def __init__(self):
        self.window = (0.0, 0.0)
        self.window_s = 0.0
        self.busy_s = 0.0           # mean over the chips used
        self.busy_by_device = {}
        self.n_device_events = 0
        self.op_seconds = {}        # device 0: name -> self seconds
        self.op_calls = {}          # device 0: name -> events
        self.op_text = {}           # device 0: name -> the op's HLO text
        self.collective_s = 0.0     # device 0
        self.collective_exposed_s = 0.0
        self.gaps = []              # device 0: (seconds, start, host name)
        self.annotations = {}       # host annotation name -> [(start, end)]

    @property
    def idle_share(self):
        return 1.0 - self.busy_by_device[min(self.busy_by_device)] \
            / self.window_s

    def count(self, annotation):
        """Whole spans of a host annotation inside the window."""
        lo, hi = self.window
        return sum(1 for s, e in self.annotations.get(annotation, ())
                   if s >= lo - 1e-9 and e <= hi + 1e-9)

    def seconds_matching(self, pattern):
        """Self seconds and calls of device-0 ops whose name matches."""
        rx = re.compile(pattern)
        hit = [n for n in self.op_seconds if rx.search(n)]
        return (sum(self.op_seconds[n] for n in hit),
                sum(self.op_calls[n] for n in hit), hit)

    def seconds_per(self, pattern, annotation):
        """Self seconds of the matching device-0 ops per whole span of a
        host annotation (per step); ``None`` when either is absent."""
        spans = self.count(annotation)
        seconds, calls, _names = self.seconds_matching(pattern)
        if not spans or not calls:
            return None
        return seconds / spans

    def top_ops(self, n):
        return [[name, sec] for name, sec in sorted(
            self.op_seconds.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n):
        return [[name, sec] for sec, _start, name in self.gaps[:n]]


def summarize(events, chips=1):
    """Reduce ``events`` over the traced window: the host's
    ``bench.window`` annotation when the benchmark opened one, else from
    the first device op's start to the last one's end."""
    s = Summary()
    device_events = collections.defaultdict(list)
    for ev in events:
        m = DEVICE_PLANE.match(ev.plane)
        if m:
            device_events[int(m.group(1))].append(ev)
        elif ev.plane == HOST_PLANE:
            s.annotations.setdefault(ev.name, []).append(
                (ev.start, ev.start + ev.dur))
    if not device_events:
        return s
    used = sorted(device_events)[:chips]
    win = s.annotations.get(WINDOW_ANNOTATION)
    if win:
        lo, hi = min(a for a, _ in win), max(b for _, b in win)
    else:
        every = [e for d in used for e in device_events[d]]
        lo = min(e.start for e in every)
        hi = max(e.start + e.dur for e in every)
    s.window, s.window_s = (lo, hi), hi - lo
    busy = {}
    for d in used:
        busy[d] = clip(union((e.start, e.start + e.dur)
                             for e in device_events[d]), lo, hi)
        s.busy_by_device[d] = total(busy[d])
        s.n_device_events += len(device_events[d])
    s.busy_s = sum(s.busy_by_device.values()) / len(used)

    d0 = used[0]
    inside = [e for e in device_events[d0]
              if e.start >= lo and e.start + e.dur <= hi]
    for ev, own in self_times(inside):
        s.op_seconds[ev.name] = s.op_seconds.get(ev.name, 0.0) + own
        s.op_calls[ev.name] = s.op_calls.get(ev.name, 0) + 1
        s.op_text[ev.name] = ev.text
    coll = union((e.start, e.start + e.dur) for e in inside
                 if COLLECTIVE.match(e.name))
    other = union((e.start, e.start + e.dur) for e in inside
                  if not COLLECTIVE.match(e.name))
    s.collective_s = total(coll)
    s.collective_exposed_s = total(subtract(coll, other))

    host = sorted(((a, b, name) for name, spans in s.annotations.items()
                   if name != WINDOW_ANNOTATION for a, b in spans),
                  key=lambda t: t[1] - t[0])
    for a, b in subtract([(lo, hi)], busy[d0]):
        mid = (a + b) / 2
        # the innermost (shortest) annotation that holds the gap's middle
        name = next((n for x, y, n in host if x <= mid <= y),
                    "no host annotation")
        s.gaps.append((b - a, a, name))
    s.gaps.sort(reverse=True)
    return s


def look(path):
    """What a trace holds, for a look by hand: every plane and line with
    its event count, then the reduction."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("plane %r" % plane.name)
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            print("  line %r: %d events; most common %s"
                  % (line.name, len(events), names.most_common(4)))
    for chips in (1, 4):
        s = summarize(load(path), chips=chips)
        if len(s.busy_by_device) < chips:
            break
        print("chips=%d window %.6fs busy %s idle share %.4f; collectives "
              "%.6fs (exposed %.6fs)"
              % (chips, s.window_s, s.busy_by_device, s.idle_share,
                 s.collective_s, s.collective_exposed_s))
        print("host annotations: %s" % dict(
            (k, len(v)) for k, v in s.annotations.items()))
        for name, sec in s.top_ops(60):
            print("  %10.6fs %6d  %-34s %s" % (sec, s.op_calls[name], name,
                                              s.op_text[name][:150]))
        for sec, start, name in s.gaps[:12]:
            print("  gap %10.6fs at %.6f  %s" % (sec, start - s.window[0],
                                                 name))


if __name__ == "__main__":
    import sys
    look(sys.argv[1])
