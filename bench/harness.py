"""The harness: finds a cell's files by name, holds the device check, runs
the driver, reads the per-layer metrics and prints the result line.

Nothing here knows a model, a traffic mix or a metric. A later PR adds

* a configuration: ``bench/configs/<config>.json`` (+ its plain reference
  ``bench/reference/<config>.py`` and a tiny ``bench/rehearsal/configs/
  <config>.json``) and an entry under ``configs`` in ``BENCHMARK.json``;
* a traffic mix: ``bench/traffic/<traffic>.json`` (+ ``bench/rehearsal/
  traffic/<traffic>.json``), naming the ``driver`` that reads it, and an
  entry under ``workloads``;
* a per-layer metric: ``bench/metrics/<name>.py`` with ``read(run)`` and
  an entry under ``per_layer``;

and edits no file that is here.
"""
import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

from bench import trace_reduce


class Refused(Exception):
    """The run may not be measured (no TPU, unknown cell, ...): exit 2,
    no result line."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import one file of the benchmark by its path (files are found by the
    names in ``BENCHMARK.json``; dots in a metric's name rule out a plain
    ``import``)."""
    name = "bench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise Refused("cannot load %s" % path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell(object):
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, root, workload, rehearse=False):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        self.benchmark = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = dict((w["name"], w) for w in self.benchmark["workloads"])
        if workload not in cells:
            raise Refused("unknown workload %r; BENCHMARK.json has %s"
                          % (workload, sorted(cells)))
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = dict((c["name"], c) for c in self.benchmark["configs"])
        self.config_name = self.entry["config"]
        self.traffic_name = self.entry["traffic"]
        if rehearse:
            # tiny stand-ins that belong to no cell
            base = os.path.join(self.bench_dir, "rehearsal")
            config_file = os.path.join(base, "configs",
                                       self.config_name + ".json")
            traffic_file = os.path.join(base, "traffic",
                                        self.traffic_name + ".json")
        else:
            config_file = os.path.join(
                root, configs[self.config_name]["file"])
            traffic_file = os.path.join(self.bench_dir, "traffic",
                                        self.traffic_name + ".json")
        self.config = load_json(config_file)
        self.traffic = load_json(traffic_file)
        self.driver_name = self.traffic["driver"]
        self.driver_file = os.path.join(self.bench_dir, "drivers",
                                        self.driver_name + ".py")
        self.reference_file = os.path.join(self.bench_dir, "reference",
                                           self.config_name + ".py")

    def metrics(self, group):
        """The entries of ``end_to_end`` or ``per_layer`` this cell
        reports: those with no ``workloads`` key, or that list it."""
        return [m for m in self.benchmark[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def metric_file(self, name):
        return os.path.join(self.bench_dir, "metrics", name + ".py")

    def work(self, name):
        """``bench/work/<name>.py``: operations and bytes from shapes."""
        return load_module(os.path.join(self.bench_dir, "work",
                                        name + ".py"))

    def reference(self):
        return load_module(self.reference_file)


def check_devices(devices, chips, peaks, rehearse):
    """The peaks row of the device the run may be measured on, or
    ``Refused``. There is no CPU fallback: a rehearsal is asked for by
    name and is told apart by the ``device`` it prints."""
    d0 = devices[0]
    if rehearse:
        if d0.platform != "cpu":
            raise Refused("--rehearse-cpu runs on the CPU only")
        return None
    if d0.platform != "tpu":
        raise Refused("JAX found platform %r, not a TPU: nothing is "
                      "measured off the chip (--rehearse-cpu rehearses "
                      "the command on tiny files)" % d0.platform)
    if d0.device_kind not in peaks:
        raise Refused("device_kind %r is not in bench/peaks.json (%s): a "
                      "device without published peaks is an error, not a "
                      "default" % (d0.device_kind, sorted(peaks)))
    if len(devices) < chips:
        raise Refused("the cell needs %d chip(s), JAX sees %d"
                      % (chips, len(devices)))
    return peaks[d0.device_kind]


class Run(object):
    """What a per-layer metric's ``read(run)`` may look at."""

    def __init__(self, cell, peaks, result, trace, memory_peak_bytes):
        self.memory_peak_bytes = memory_peak_bytes
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.peaks = peaks                  # None in a rehearsal
        self.driver = cell.driver_name
        self.end_to_end = result["end_to_end"]
        self.samples = result.get("samples", {})
        self.counters = result.get("counters", {})
        self.trace = trace                  # trace_reduce.Summary or None

    def work(self, name):
        return self.cell.work(name)


class Context(object):
    """What a driver's ``run(ctx)`` gets."""

    def __init__(self, cell, args, t0, devices):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.t0 = t0
        self.devices = devices[:cell.chips]
        # traces are reduced in the process and removed; the directory is
        # inside the checkout and git-ignored
        self.trace_dir = os.path.join(cell.root, ".bench_out", "trace",
                                      cell.name)

    def fresh_trace_dir(self):
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir)
        return self.trace_dir


def say(msg):
    print("[bench] " + msg, flush=True)


def read_per_layer(cell, run):
    out = {}
    for entry in cell.metrics("per_layer"):
        path = cell.metric_file(entry["name"])
        if not os.path.exists(path):
            say("per-layer metric %s has no reader at %s"
                % (entry["name"], os.path.relpath(path, cell.root)))
            continue
        mod = load_module(path)
        if run.driver not in mod.DRIVERS:
            continue
        value = mod.read(run)
        if value is None:
            # nothing to read (no trace, no such events): left out
            say("per-layer metric %s: nothing to read" % entry["name"])
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def memory_peak_bytes(devices):
    """Peak HBM held on the fullest of ``devices``, as JAX reports it:
    ``memory_stats()``' ``peak_bytes_in_use`` (the allocator's buffers:
    parameters, batch, pool, outputs) plus ``peak_bytes_reserved`` (what
    the runtime sets aside for the running programs' temporaries — on the
    v5e the fused step's 5.5 GB of activations are there and nowhere in
    ``peak_bytes_in_use``; the two are disjoint). A driver reads it when
    the window closes, before the reference comparisons allocate theirs."""
    peak = 0
    for d in devices:
        ms = d.memory_stats() or {}
        say("memory_stats %s: %s" % (d, json.dumps(ms, sort_keys=True)))
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0))
                   + int(ms.get("peak_bytes_reserved", 0)))
    return peak


def device_block(all_devices, peak, trace):
    d0 = all_devices[0]
    block = {"platform": d0.platform, "kind": d0.device_kind,
             "count": len(all_devices), "memory_peak_bytes": int(peak)}
    if trace is not None:
        block["busy_s"] = trace.busy_s
        block["window_s"] = trace.window_s
    return block


def main(argv, root, t0):
    ap = argparse.ArgumentParser(description="run one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny rehearsal on the host CPU: proves the "
                         "command, measures nothing")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        # force the platform BEFORE jax is imported
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    try:
        cell = Cell(root, args.workload, rehearse=args.rehearse_cpu)
        peaks_table = load_json(os.path.join(cell.bench_dir, "peaks.json"))
        try:
            import jax
            import mxnet_tpu  # noqa: F401  places the compile cache
        except ImportError as e:
            raise Refused("cannot import the program under test (%s); run "
                          "from a checkout of the repository" % e)
        all_devices = jax.devices()
        peaks = check_devices(all_devices, cell.chips,
                              peaks_table["devices"], args.rehearse_cpu)
        d0 = all_devices[0]
        say("device platform=%s device_kind=%r count=%d; cell %s needs %d"
            % (d0.platform, d0.device_kind, len(all_devices), cell.name,
               cell.chips))
        if args.rehearse_cpu:
            say("REHEARSAL on the host CPU with tiny files: no number of "
                "this run is a measurement")
        driver = load_module(cell.driver_file)
        ctx = Context(cell, args, t0, all_devices)
        result = driver.run(ctx)
    except Refused as e:
        print("bench: refused: %s" % e, file=sys.stderr, flush=True)
        return 2

    trace = None
    if result.get("trace_path"):
        t_red = time.perf_counter()
        trace = trace_reduce.summarize(
            trace_reduce.load(result["trace_path"]), chips=cell.chips)
        say("trace reduced in %.1fs: window %.3fs, busy %.3fs, %d device "
            "op events" % (time.perf_counter() - t_red, trace.window_s,
                           trace.busy_s, trace.n_device_events))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    device = device_block(all_devices, result["memory_peak_bytes"], trace)
    run = Run(cell, peaks, result, trace, device["memory_peak_bytes"])

    for name, ok, detail in result["checks"]:
        say("check %-28s %s %s" % (name, "ok  " if ok else "FAIL", detail))
    correct = all(ok for _n, ok, _d in result["checks"])
    e2e = dict((m["name"], {"value": float(result["end_to_end"][m["name"]]),
                            "unit": m["unit"]})
               for m in cell.metrics("end_to_end"))
    say("end to end: %s" % json.dumps(e2e, sort_keys=True))
    if args.trace:
        metrics = read_per_layer(cell, run)
    else:
        metrics = e2e
    line = {"correct": bool(correct),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics,
            "device": device}
    if trace is not None:
        line["breakdown"] = {"device_ops": trace.top_ops(10),
                             "idle_gaps": trace.top_gaps(5)}
    print(json.dumps(line), flush=True)
    return 0
