"""Compiler forensics: per-program HLO capture, fusion-boundary
roofline attribution, and cross-run regression diffing.

PR 12 gave every compiled program a measured MFU and an XLA cost
analysis; PR 14 put every program behind one registry. This module is
the bridge from "we measure MFU" to "we know which fusion to burn
down": for any program in the :mod:`mxnet_tpu.programs` registry it
captures the *optimized* HLO (``lower(...).compile().as_text()`` —
post-fusion, scheduled), parses the module into a per-fusion inventory,
and emits a **forensics report** ranking fusions by bytes moved against
the program's measured MFU gap, with the residual (unfused elementwise
chains, copies/transposes, host round-trips) called out.

Analysis frame ("Operator Fusion in XLA", PAPERS.md): the fusion
boundary is the unit of bytes-moved attribution — everything inside a
fusion stays in registers/VMEM, only operands and results cross HBM.
So a fusion's ``bytes`` here is its *boundary* bytes (operands +
outputs), its ``flops`` the estimated work of its op roster, and the
per-program sum reconciles with the compiled module's own
``cost_analysis()`` totals within a documented tolerance
(``reconciliation`` in every report; see docs/observability.md).

Capture runs entirely under ``telemetry.suppress_compile_tracking()``:
the AOT ``lowered.compile()`` is a persistent-cache disk load (the
program was just compiled and cached by the jit site) and its events never touch the compile
counters, so every zero-recompile assertion in the serving/training
tests stays honest. Nothing runs per step — capture is once per
program fingerprint.

Reports are content-addressed artifacts: ``<dir>/<fingerprint>.json``
written via ``checkpoint.atomic_writer`` with an embedded CRC32, where
``<dir>`` is ``MXNET_FORENSICS_DIR`` or
``<programs.cache_dir()>/forensics``. The fingerprint is the
registry ``ProgramKey`` fingerprint — it already folds in the
jax/jaxlib/backend version salt — so the SAME logical program captured
under two jax versions or flag sets lands as two files, and
:func:`diff` can flag fusion regressions between them (a fusion that
split, a new copy, >X% boundary-bytes growth). A regression records a
``forensics`` flight-recorder event.

Surfaces:

* ``GET /programs`` on both ``telemetry.serve()`` and
  ``serve.serve_http`` (:func:`programs_endpoint` — registry listing;
  ``?key=<fingerprint>`` returns the per-program forensics summary).
* ``python -m mxnet_tpu.forensics <report|dir> [--diff A B] [--json]``
  (the blackbox CLI pattern; ``--diff`` exits 1 on a regression).
* ``mxnet_tpu.diagnostics()`` carries :func:`worst_fusions` — the
  top-N fusions by ``bytes_share x (1 - measured MFU)``, from the
  reports captured in this process.

On backends without compiled-HLO text or cost analysis the capture
degrades to an ``unavailable`` report stanza plus
``forensics/unavailable_total`` — never a raise on the serve path
(the PR 12 ``cost_analysis_unavailable_total`` pattern).

Enable with ``MXNET_FORENSICS=1`` (or :func:`configure`). Disabled,
a capture site pays one config lookup per *program* (not per step).
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import re
import threading
import time
import zlib

from .base import MXNetError

_log = logging.getLogger("mxnet_tpu.forensics")

__all__ = ["enabled", "configure", "reports_dir", "maybe_capture",
           "analyze_hlo", "reports", "report_for", "load_report",
           "write_report", "reports_on_disk", "diff", "summary",
           "worst_fusions", "measured_mfu",
           "programs_endpoint", "main", "reset"]

FORMAT = 1

# documented reconciliation tolerance: the parser's shape-based
# estimates vs the compiled module's cost_analysis() totals. FLOPs are
# dominated by dot/conv (both sides count 2*M*N*K) so they reconcile
# tightly; bytes differ more (XLA's "bytes accessed" weights operand
# reuse, the parser counts raw boundary crossings), hence the wider
# band. Reports carry the measured ratio either way.
FLOPS_TOLERANCE = 0.5       # parsed/cost_analysis in [1/(1+t), 1+t+...]
BYTES_TOLERANCE = 3.0       # parsed within [1/4, 4]x of cost_analysis

_lock = threading.Lock()
_reports = {}               # fingerprint -> report dict (this process)
_enabled_override = None    # configure() beats MXNET_FORENSICS
_dir_override = None


def _config(name, fallback=None):
    try:
        from .config import get
        v = get(name)
        return fallback if v in (None, "") else v
    except Exception:
        return fallback


def _tm():
    from . import telemetry
    return telemetry


def enabled():
    """Capture on/off: :func:`configure` override, else
    ``MXNET_FORENSICS``."""
    if _enabled_override is not None:
        return _enabled_override
    return bool(_config("MXNET_FORENSICS", 0))


def configure(on=None, directory=None):
    """Runtime override of ``MXNET_FORENSICS[_DIR]`` (pass ``on=False``
    to force off, ``None`` leaves that knob on its env value). Returns
    the previous (on, directory) overrides."""
    global _enabled_override, _dir_override
    prev = (_enabled_override, _dir_override)
    _enabled_override = None if on is None else bool(on)
    _dir_override = None if directory is None \
        else os.path.abspath(os.fspath(directory))
    return prev


def reports_dir():
    """Where report artifacts land: ``MXNET_FORENSICS_DIR`` (or the
    :func:`configure` override), else ``<compile cache dir>/forensics``."""
    if _dir_override is not None:
        return _dir_override
    d = _config("MXNET_FORENSICS_DIR")
    if d:
        return os.path.abspath(d)
    from . import programs as _pg
    return os.path.join(_pg.cache_dir(), "forensics")


# ---------------------------------------------------------------------------
# optimized-HLO text parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1,
    "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
}

_SHAPE_RE = re.compile(
    r"\b(pred|bf16|f8e[a-z0-9]+|f16|f32|f64|s4|s8|s16|s32|s64|"
    r"u4|u8|u16|u32|u64|c64|c128)\[([0-9,]*)\]")
_COMP_RE = re.compile(
    r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")

# estimator op classes (HLO opcode spellings)
_FREE_OPS = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "reshape", "after-all", "partition-id", "replica-id",
    "opt-barrier"))
_COPY_OPS = frozenset(("copy", "copy-start", "copy-done"))
_HOST_OPS = frozenset((
    "custom-call", "infeed", "outfeed", "send", "recv", "send-done",
    "recv-done"))
_ZERO_FLOP_OPS = frozenset((
    "broadcast", "slice", "concatenate", "pad", "reverse", "gather",
    "dynamic-slice", "dynamic-update-slice", "iota", "transpose",
    "convert", "rng-bit-generator", "rng-get-and-update-state", "rng",
    "bitcast-convert", "copy", "copy-start", "copy-done",
    "all-gather", "all-to-all", "collective-permute")) | _FREE_OPS


def _dims(dims_str):
    if not dims_str:
        return ()
    return tuple(int(d) for d in dims_str.split(",") if d != "")


def _prod(dims):
    n = 1
    for d in dims:
        n *= d
    return n


def _type_elems_bytes(type_str):
    """(elements, bytes) summed over every shape token in ``type_str``
    (a tuple type sums its leaves; a scalar ``f32[]`` is 1 element)."""
    elems = nbytes = 0
    for dtype, dims_str in _SHAPE_RE.findall(type_str):
        n = _prod(_dims(dims_str))
        elems += n
        nbytes += n * _DTYPE_BYTES.get(dtype, 4)
    return elems, nbytes


def _split_instr(rhs):
    """``rhs`` of one ``%name = ...`` line -> (output_type, opcode,
    rest) where ``rest`` starts at the operand group."""
    rhs = rhs.strip()
    if rhs.startswith("("):              # tuple output type
        depth = 0
        end = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        ty, rest = rhs[:end + 1], rhs[end + 1:].strip()
    else:
        ty, _, rest = rhs.partition(" ")
    m = re.match(r"([\w\-]+)\s*\(", rest)
    opcode = m.group(1) if m else rest.split("(", 1)[0].strip()
    return ty, opcode, rest


def _operand_group(rest, opcode):
    """The text inside the operand parens of ``rest`` (which begins at
    ``opcode(``), plus the attr tail after the closing paren."""
    start = rest.find("(", len(opcode))
    if start < 0:
        return "", ""
    depth = 0
    for i in range(start, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                return rest[start + 1:i], rest[i + 1:]
    return rest[start + 1:], ""


def _shape_clean(type_str):
    """Layout-free shape for display: ``f32[8,128]{1,0}`` ->
    ``f32[8,128]`` (tuples keep every leaf)."""
    toks = ["%s[%s]" % (d, s) for d, s in _SHAPE_RE.findall(type_str)]
    if not toks:
        return type_str.strip()
    return toks[0] if len(toks) == 1 else "(%s)" % ", ".join(toks)


def _est_flops(opcode, out_ty, operands, attrs):
    """Shape-based FLOP estimate for one instruction. ``operands`` is
    the operand-group text (typed operands), ``attrs`` the tail after
    the closing paren (contracting dims, window, dim_labels)."""
    out_elems, _ = _type_elems_bytes(out_ty)
    op_shapes = _SHAPE_RE.findall(operands)
    if opcode in _ZERO_FLOP_OPS:
        return 0.0
    if opcode == "dot":
        k = 0
        if op_shapes:
            lhs = _dims(op_shapes[0][1])
            m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", attrs)
            if m and lhs:
                try:
                    k = _prod([lhs[int(i)] for i in
                               m.group(1).split(",") if i != ""])
                except (IndexError, ValueError):
                    k = 0
        if not k:
            k = _prod(_dims(op_shapes[0][1])) if op_shapes else 1
            k = max(1, int(round(k ** 0.5)))     # last-resort guess
        return 2.0 * out_elems * k
    if opcode == "convolution":
        kern = _dims(op_shapes[1][1]) if len(op_shapes) > 1 else ()
        kern_elems = _prod(kern) if kern else 1
        co = 1
        m = re.search(r"dim_labels=\w+_(\w+)->", attrs)
        if m and kern and "o" in m.group(1):
            idx = m.group(1).index("o")
            if idx < len(kern):
                co = max(1, kern[idx])
        return 2.0 * out_elems * kern_elems / co
    if opcode in ("reduce", "reduce-window", "sort", "select-and-scatter",
                  "scatter", "all-reduce", "reduce-scatter"):
        in_elems = _prod(_dims(op_shapes[0][1])) if op_shapes else out_elems
        return float(max(in_elems, out_elems))
    # elementwise / transcendental / compare / select / unknown: one
    # flop per output element (XLA's own default convention)
    return float(out_elems)


def _inst_bytes(out_ty, operands):
    """Boundary bytes of one instruction: operand reads + result
    writes (raw shape bytes; no reuse weighting)."""
    _, ob = _type_elems_bytes(out_ty)
    _, ib = _type_elems_bytes(operands)
    return float(ib + ob)


def _parse_computations(text):
    """{name: [(name, out_ty, opcode, operands, attrs), ...]} plus the
    entry computation's name."""
    comps, entry = {}, None
    cur = None
    for line in text.splitlines():
        stripped = line.strip()
        if cur is not None:
            if stripped == "}" or stripped.startswith("}"):
                cur = None
                continue
            m = _INSTR_RE.match(line)
            if not m:
                continue
            name, rhs = m.group(1), m.group(2)
            ty, opcode, rest = _split_instr(rhs)
            operands, attrs = _operand_group(rest, opcode)
            comps[cur].append((name, ty, opcode, operands, attrs))
            continue
        m = _COMP_RE.match(line)
        if m and "=" not in line.split("(", 1)[0]:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
    return comps, entry


def analyze_hlo(text):
    """Parse one optimized HLO module into the per-fusion inventory.

    Returns ``{"fusions": [...], "residual": {...}, "totals": {...}}``:
    each fusion row carries its kind (kLoop/kInput/kOutput), op roster,
    output shape, estimated flops and *boundary* bytes (operands +
    outputs — the bytes that cross HBM, per the fusion-boundary
    analysis frame), and its share of the module's total bytes; the
    residual groups the unfused top-level ops with copies/transposes
    and host round-trips (custom-call/infeed/outfeed) called out.
    """
    comps, entry = _parse_computations(text)
    if entry is None:
        raise MXNetError("no ENTRY computation in HLO text")

    def _comp_flops_and_roster(cname):
        roster, flops = {}, 0.0
        for _n, ty, opcode, operands, attrs in comps.get(cname, ()):
            if opcode in ("parameter", "constant"):
                continue
            roster[opcode] = roster.get(opcode, 0) + 1
            flops += _est_flops(opcode, ty, operands, attrs)
        return roster, flops

    fusions = []
    residual = {"ops": {}, "copies": 0, "transposes": 0,
                "host_round_trips": 0, "flops": 0.0, "bytes": 0.0}
    n_instr = 0
    for name, ty, opcode, operands, attrs in comps[entry]:
        if opcode in _FREE_OPS:
            continue
        n_instr += 1
        if opcode == "fusion":
            kind = "?"
            m = re.search(r"kind=(k\w+)", attrs)
            if m:
                kind = m.group(1)
            called = None
            m = re.search(r"calls=%?([\w.\-]+)", attrs)
            if m:
                called = m.group(1)
            roster, flops = _comp_flops_and_roster(called)
            fusions.append({
                "name": name, "kind": kind, "ops": roster,
                "output": _shape_clean(ty), "flops": flops,
                "bytes": _inst_bytes(ty, operands)})
            continue
        nbytes = _inst_bytes(ty, operands)
        residual["ops"][opcode] = residual["ops"].get(opcode, 0) + 1
        residual["flops"] += _est_flops(opcode, ty, operands, attrs)
        residual["bytes"] += nbytes
        if opcode in _COPY_OPS:
            residual["copies"] += 1
        elif opcode == "transpose":
            residual["transposes"] += 1
        elif opcode in _HOST_OPS:
            residual["host_round_trips"] += 1

    total_bytes = sum(f["bytes"] for f in fusions) + residual["bytes"]
    total_flops = sum(f["flops"] for f in fusions) + residual["flops"]
    for f in fusions:
        f["bytes_share"] = round(f["bytes"] / total_bytes, 4) \
            if total_bytes else 0.0
    fusions.sort(key=lambda f: -f["bytes"])
    residual["flops"] = round(residual["flops"], 1)
    residual["bytes"] = round(residual["bytes"], 1)
    return {"fusions": fusions, "residual": residual,
            "totals": {"instructions": n_instr, "fusions": len(fusions),
                       "flops": round(total_flops, 1),
                       "bytes": round(total_bytes, 1)}}


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------

def maybe_capture(pkey, jitted=None, args=(), kwargs=None, cost=None,
                  lowered=None):
    """Capture one program's forensics report (once per fingerprint).

    Called by ``health.capture_cost`` right after the cost analysis
    registers, with the live jitted + args it already holds (and its
    ``lowered`` object, so the module is not re-traced). The AOT
    ``lowered.compile()`` runs under ``suppress_compile_tracking`` —
    a persistent-cache disk load when a cache dir is wired, and in
    either case invisible to the compile counters. Never raises: on a
    backend without compiled-HLO text the stored report degrades to
    the documented ``unavailable`` stanza and
    ``forensics/unavailable_total`` ticks.

    Returns the report dict, or None when capture is disabled.
    """
    if not enabled() or pkey is None:
        return None
    fp = pkey.fingerprint
    with _lock:
        if fp in _reports:
            return _reports[fp]
    tm = _tm()
    d = reports_dir()
    if d is not None:
        # same fingerprint == same program identity (the salt folds in
        # jax/jaxlib/backend): an earlier process already paid for this
        # capture, adopt its artifact instead of re-compiling
        prior = load_report(_report_path(d, fp), quiet=True)
        if prior is not None and not prior.get("unavailable"):
            with _lock:
                _reports.setdefault(fp, prior)
            if tm._enabled:
                tm.counter("forensics/captured_total",
                           "Forensics reports captured (per-fusion HLO "
                           "inventory; includes artifacts adopted from "
                           "the forensics dir)", ("kind",)
                           ).labels(pkey.kind).inc()
            return prior
    from . import programs as _pg
    report = {"format": FORMAT, "fingerprint": fp, "kind": pkey.kind,
              "graph": pkey.graph, "spec": pkey.spec,
              "salt": _pg.version_salt(),
              "captured": time.strftime("%Y-%m-%dT%H:%M:%S")}
    if cost:
        report["cost_analysis"] = {"flops": cost.get("flops", 0.0),
                                   "bytes": cost.get("bytes", 0.0)}
    try:
        with tm.suppress_compile_tracking():
            if lowered is None:
                if jitted is None:
                    raise MXNetError("no jitted/lowered to capture")
                lowered = jitted.lower(*args, **(kwargs or {}))
            compiled = lowered.compile()
            text = compiled.as_text()
            if not text or "ENTRY" not in text:
                raise MXNetError("backend returned no compiled HLO text")
            if "cost_analysis" not in report:
                try:
                    ca = compiled.cost_analysis()
                    if isinstance(ca, (list, tuple)):
                        ca = ca[0] if ca else None
                    if ca:
                        report["cost_analysis"] = {
                            "flops": float(ca.get("flops", 0.0)),
                            "bytes": float(ca.get("bytes accessed", 0.0))}
                except Exception:
                    pass
        report["hlo_sha256"] = hashlib.sha256(text.encode()).hexdigest()
        report.update(analyze_hlo(text))
        ca = report.get("cost_analysis")
        if ca and ca.get("flops"):
            recon = {"flops_ratio":
                     round(report["totals"]["flops"] / ca["flops"], 3)}
            if ca.get("bytes"):
                recon["bytes_ratio"] = round(
                    report["totals"]["bytes"] / ca["bytes"], 3)
            recon["flops_tolerance"] = FLOPS_TOLERANCE
            recon["bytes_tolerance"] = BYTES_TOLERANCE
            report["reconciliation"] = recon
        if tm._enabled:
            tm.counter("forensics/captured_total",
                       "Forensics reports captured (per-fusion HLO "
                       "inventory; includes artifacts adopted from "
                       "the forensics dir)", ("kind",)
                       ).labels(pkey.kind).inc()
    except Exception as e:              # backend without HLO text
        report["unavailable"] = True
        report["reason"] = "%s: %s" % (type(e).__name__, e)
        report["stanza"] = (
            "n/a - backend offers no compiled HLO text / cost "
            "analysis; forensics degraded (forensics/unavailable_total)")
        if tm._enabled:
            tm.counter("forensics/unavailable_total",
                       "Programs whose backend offered no compiled HLO "
                       "text or cost analysis (forensics degrades to an "
                       "n/a report stanza)", ("kind",)
                       ).labels(pkey.kind).inc()
        _log.debug("forensics unavailable for %s: %s", pkey, e)
    with _lock:
        _reports[fp] = report
    try:
        write_report(report)
    except Exception as e:              # disk full must not break serve
        _log.debug("forensics report write failed for %s: %s", fp, e)
    return report


# ---------------------------------------------------------------------------
# report artifacts (CRC-framed, atomic)
# ---------------------------------------------------------------------------

def _report_path(directory, fp):
    return os.path.join(directory, "%s.json" % fp)


def write_report(report, directory=None):
    """Write one report as a content-addressed artifact
    (``<dir>/<fingerprint>.json``, ``checkpoint.atomic_writer``, CRC32
    over the canonical report body). Returns the path, or None when no
    directory is configured."""
    d = directory or reports_dir()
    if not d:
        return None
    from .checkpoint import atomic_writer
    os.makedirs(d, exist_ok=True)
    body = json.dumps(report, sort_keys=True, default=str)
    doc = {"format": FORMAT,
           "crc32": zlib.crc32(body.encode()) & 0xFFFFFFFF,
           "report": json.loads(body)}
    path = _report_path(d, report["fingerprint"])
    with atomic_writer(path, "w") as f:
        json.dump(doc, f, sort_keys=True)
        f.write("\n")
    return path


def load_report(path, quiet=False):
    """Load + CRC-verify one report file. Returns the report dict, or
    None on a missing/torn/corrupt file (counted in
    ``forensics/reports_corrupt_total`` unless the file simply does
    not exist)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        body = json.dumps(doc["report"], sort_keys=True)
        if (zlib.crc32(body.encode()) & 0xFFFFFFFF) != doc["crc32"]:
            raise ValueError("crc mismatch")
        return doc["report"]
    except FileNotFoundError:
        return None
    except (OSError, ValueError, KeyError, TypeError) as e:
        tm = _tm()
        if tm._enabled:
            tm.counter("forensics/reports_corrupt_total",
                       "Forensics report files skipped for a CRC/parse "
                       "failure during the fallback walk").inc()
        if not quiet:
            _log.warning("corrupt forensics report %s: %s", path, e)
        return None


def reports_on_disk(directory=None):
    """{fingerprint: report} from every loadable ``*.json`` under the
    forensics dir — the fallback walk: torn/corrupt files are counted
    and skipped, never raised."""
    d = directory or reports_dir()
    out = {}
    if not d or not os.path.isdir(d):
        return out
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".json"):
            continue
        rep = load_report(os.path.join(d, fn))
        if rep is not None and "fingerprint" in rep:
            out[rep["fingerprint"]] = rep
    return out


def reports():
    """{fingerprint: report} captured by THIS process."""
    with _lock:
        return dict(_reports)


def report_for(fp):
    """One report by fingerprint: in-memory first, then the forensics
    dir. None when never captured."""
    with _lock:
        rep = _reports.get(fp)
    if rep is not None:
        return rep
    d = reports_dir()
    return load_report(_report_path(d, fp), quiet=True) if d else None


# ---------------------------------------------------------------------------
# cross-run diff
# ---------------------------------------------------------------------------

def _fusion_sig(f):
    """Fusion identity across runs: op roster + output shape (names
    like ``%fused_computation.3`` are not stable across compiles)."""
    return (tuple(sorted(f.get("ops", {}).items())), f.get("output"))


def diff(a, b, bytes_growth_pct=10.0, record=True):
    """Compare two forensics reports (A = baseline, B = candidate) and
    flag fusion regressions.

    Flags: fusion-count growth (a fusion split, or new fusions XLA
    used to avoid), matched-fusion boundary-bytes growth past
    ``bytes_growth_pct``, new copies/transposes in the residual, new
    host round-trips, and total-bytes growth past the threshold.
    Fusions are matched by (op roster, output shape) — fusion *names*
    are compiler-generated and not stable across runs. A regression
    records a ``forensics`` flight-recorder event and ticks
    ``forensics/diff_regressions_total`` (``record=False`` to
    suppress, e.g. when re-reading a CLI diff).
    """
    out = {"a": a.get("fingerprint"), "b": b.get("fingerprint"),
           "kind": a.get("kind"),
           "salt_a": a.get("salt"), "salt_b": b.get("salt"),
           "comparable": True, "changes": [], "regressions": []}
    if a.get("unavailable") or b.get("unavailable"):
        out["comparable"] = False
        out["changes"].append("one side is an unavailable stanza")
        return out
    fa = {f["name"]: f for f in a.get("fusions", ())}
    fb = {f["name"]: f for f in b.get("fusions", ())}
    ca, cb = len(fa), len(fb)
    out["fusion_count"] = {"a": ca, "b": cb}
    if cb > ca:
        out["regressions"].append(
            "fusion count grew %d -> %d (a fusion split, or work XLA "
            "previously fused now runs as separate kernels)" % (ca, cb))
    elif cb < ca:
        out["changes"].append("fusion count shrank %d -> %d" % (ca, cb))

    def _by_sig(fus):
        m = {}
        for f in fus.values():
            m.setdefault(_fusion_sig(f), []).append(f)
        return m
    siga, sigb = _by_sig(fa), _by_sig(fb)
    for sig, fl in siga.items():
        if sig not in sigb:
            out["changes"].append(
                "fusion gone: %s -> %s" % (dict(sig[0]), sig[1]))
    for sig, fl in sigb.items():
        if sig not in siga:
            out["changes"].append(
                "fusion new: %s -> %s" % (dict(sig[0]), sig[1]))
            continue
        ba = sum(f["bytes"] for f in siga[sig]) / max(len(siga[sig]), 1)
        bb = sum(f["bytes"] for f in fl) / max(len(fl), 1)
        if ba > 0:
            growth = (bb - ba) / ba * 100.0
            if growth > bytes_growth_pct:
                out["regressions"].append(
                    "fusion %s -> %s boundary bytes grew %.1f%% "
                    "(%.0f -> %.0f)" % (dict(sig[0]), sig[1], growth,
                                        ba, bb))
    ra = a.get("residual", {})
    rb = b.get("residual", {})
    for field, what in (("copies", "copies"),
                        ("transposes", "transposes"),
                        ("host_round_trips", "host round-trips")):
        da, db = ra.get(field, 0), rb.get(field, 0)
        if db > da:
            out["regressions"].append(
                "%d new %s in the residual (%d -> %d)"
                % (db - da, what, da, db))
    ta = a.get("totals", {}).get("bytes", 0.0)
    tb = b.get("totals", {}).get("bytes", 0.0)
    if ta > 0:
        growth = (tb - ta) / ta * 100.0
        out["total_bytes_growth_pct"] = round(growth, 2)
        if growth > bytes_growth_pct:
            out["regressions"].append(
                "total boundary bytes grew %.1f%% (%.0f -> %.0f)"
                % (growth, ta, tb))
    out["regressed"] = bool(out["regressions"])
    if out["regressed"] and record:
        tm = _tm()
        if tm._enabled:
            tm.counter("forensics/diff_regressions_total",
                       "Forensics diffs that flagged a fusion "
                       "regression (split fusion, new copy, bytes "
                       "growth)").inc()
        try:
            from . import blackbox as _bb
            _bb.record_event("forensics", a=out["a"], b=out["b"],
                             kind=out["kind"],
                             regressions=out["regressions"][:8])
        except Exception:
            pass
    return out


# ---------------------------------------------------------------------------
# roofline join + summaries
# ---------------------------------------------------------------------------

# which live MFU gauge prices a program kind (serve buckets ride the
# executor forward capture; decode gauges are phase-labeled)
_MFU_GAUGE = {"fused_step": ("executor/mfu", None),
              "executor_forward": ("serving/mfu", None),
              "serve_bucket": ("serving/mfu", None),
              "decode_prefill": ("decode/mfu", "prefill"),
              "decode_step": ("decode/mfu", "step")}


def measured_mfu(kind):
    """Best live measured MFU for a program kind (max over gauge
    labels), or None when nothing has been measured yet."""
    spec = _MFU_GAUGE.get(kind)
    if spec is None:
        return None
    tm = _tm()
    fam = tm.REGISTRY._families.get(spec[0])
    if fam is None:
        return None
    vals = [c.value for lv, c in fam.series()
            if spec[1] is None or (lv and lv[0] == spec[1])]
    return max(vals) if vals else None


def summary(report):
    """Compact per-program summary (the ``/programs?key=`` body): top
    fusions by boundary bytes, residual, reconciliation, and the
    measured-MFU roofline join (``gap`` = 1 - measured MFU; a
    memory-bound program with one dominant fusion and a big gap names
    its own burn-down target)."""
    if report.get("unavailable"):
        return {k: report.get(k) for k in
                ("fingerprint", "kind", "captured", "salt",
                 "unavailable", "reason", "stanza")}
    mfu = measured_mfu(report.get("kind"))
    out = {"fingerprint": report.get("fingerprint"),
           "kind": report.get("kind"),
           "captured": report.get("captured"),
           "salt": report.get("salt"),
           "totals": report.get("totals"),
           "residual": report.get("residual"),
           "cost_analysis": report.get("cost_analysis"),
           "reconciliation": report.get("reconciliation"),
           "fusions_top": report.get("fusions", [])[:8],
           "mfu_measured": None if mfu is None else round(mfu, 6),
           "mfu_gap": None if mfu is None
           else round(max(0.0, 1.0 - mfu), 6)}
    return out


def worst_fusions(limit=5):
    """Top-N fusions across every captured program, ranked by
    ``bytes_share x (1 - measured MFU)`` — the biggest byte movers in
    the programs farthest from the roofline (the ``diagnostics()``
    table; unmeasured programs rank by bytes_share alone)."""
    rows = []
    for fp, rep in reports().items():
        if rep.get("unavailable"):
            continue
        mfu = measured_mfu(rep.get("kind"))
        gap = None if mfu is None else max(0.0, 1.0 - mfu)
        for f in rep.get("fusions", ())[:limit]:
            rows.append({
                "program": fp[:12], "kind": rep.get("kind"),
                "fusion": f["name"], "ops": f["ops"],
                "output": f["output"], "bytes": f["bytes"],
                "bytes_share": f["bytes_share"],
                "mfu": None if mfu is None else round(mfu, 4),
                "gap": None if gap is None else round(gap, 4),
                "score": round(f["bytes_share"] *
                               (1.0 if gap is None else gap), 4)})
    rows.sort(key=lambda r: -r["score"])
    return rows[:limit]


# ---------------------------------------------------------------------------
# GET /programs (both HTTP mounts)
# ---------------------------------------------------------------------------

def programs_endpoint(query=""):
    """(status_code, payload) for ``GET /programs`` — the one
    implementation behind both mounts (telemetry.serve and
    serve.serve_http; the traces/alerts endpoint pattern). Bare:
    the registry listing with forensics availability per program.
    ``?key=<fingerprint>``: that program's forensics summary."""
    import urllib.parse
    from . import programs as _pg
    q = urllib.parse.parse_qs(query or "")
    key = (q.get("key") or [None])[0]
    if key:
        rep = report_for(key)
        row = _pg.entries().get(key)
        if rep is None and row is None:
            return 404, {"error": "unknown program %r (not in the "
                                  "registry, no forensics report)" % key}
        return 200, {"fingerprint": key, "registry": row,
                     "forensics": None if rep is None else summary(rep)}
    captured = set(reports())
    on_disk = set(reports_on_disk())
    rows = {}
    for fp, row in _pg.entries().items():
        row = dict(row)
        row["forensics"] = (fp in captured or fp in on_disk)
        rows[fp] = row
    return 200, {"programs": rows, "count": len(rows),
                 "forensics": {"enabled": enabled(),
                               "dir": reports_dir(),
                               "captured": len(captured),
                               "on_disk": len(on_disk)}}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _fmt_report(rep):
    lines = ["program %s  kind=%s  captured=%s" % (
        rep.get("fingerprint"), rep.get("kind"), rep.get("captured"))]
    lines.append("  salt: %s" % rep.get("salt"))
    if rep.get("unavailable"):
        lines.append("  UNAVAILABLE: %s" % rep.get("reason"))
        lines.append("  %s" % rep.get("stanza"))
        return "\n".join(lines)
    t = rep.get("totals", {})
    lines.append("  totals: %d instrs, %d fusions, %.3g flops, "
                 "%.3g bytes" % (t.get("instructions", 0),
                                 t.get("fusions", 0),
                                 t.get("flops", 0), t.get("bytes", 0)))
    recon = rep.get("reconciliation")
    if recon:
        lines.append("  reconciliation vs cost_analysis: flops x%.3f"
                     % recon["flops_ratio"]
                     + (", bytes x%.3f" % recon["bytes_ratio"]
                        if "bytes_ratio" in recon else ""))
    lines.append("  %-9s %-28s %-22s %12s %8s" %
                 ("kind", "ops", "output", "bytes", "share"))
    for f in rep.get("fusions", ())[:20]:
        ops = ",".join("%s:%d" % kv for kv in sorted(f["ops"].items()))
        lines.append("  %-9s %-28s %-22s %12.0f %7.1f%%" %
                     (f["kind"], ops[:28], f["output"][:22], f["bytes"],
                      f["bytes_share"] * 100))
    r = rep.get("residual", {})
    lines.append("  residual: %s  (copies=%d transposes=%d host=%d, "
                 "%.3g bytes)" % (dict(r.get("ops", {})),
                                  r.get("copies", 0),
                                  r.get("transposes", 0),
                                  r.get("host_round_trips", 0),
                                  r.get("bytes", 0)))
    return "\n".join(lines)


def _resolve_report(token, base):
    """CLI report lookup: a file path, or a fingerprint (prefix) under
    the ``base`` directory."""
    if os.path.isfile(token):
        return load_report(token)
    d = base if base and os.path.isdir(base) else reports_dir()
    if d and os.path.isdir(d):
        cand = _report_path(d, token)
        if os.path.isfile(cand):
            return load_report(cand)
        hits = [fn for fn in sorted(os.listdir(d))
                if fn.startswith(token) and fn.endswith(".json")]
        if len(hits) == 1:
            return load_report(os.path.join(d, hits[0]))
    return None


def main(argv=None):
    """``python -m mxnet_tpu.forensics <report|dir> [--diff A B]
    [--json]`` — print one report, list a forensics dir, or diff two
    reports (exit 1 when the diff flags a regression)."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.forensics",
        description="inspect forensics reports: per-fusion HLO "
                    "inventory, roofline attribution, cross-run diff")
    ap.add_argument("path", help="a forensics report file, or the "
                                 "forensics/ directory")
    ap.add_argument("--diff", nargs=2, metavar=("A", "B"),
                    help="diff two reports (paths, or fingerprint "
                         "prefixes under PATH); exits 1 on regression")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    args = ap.parse_args(argv)

    if args.diff:
        a = _resolve_report(args.diff[0], args.path)
        b = _resolve_report(args.diff[1], args.path)
        if a is None or b is None:
            print("cannot load %r" % args.diff[a is not None])
            return 2
        d = diff(a, b, record=False)
        if args.json:
            print(json.dumps(d, sort_keys=True))
        else:
            print("diff %s -> %s (%s)" % (d["a"], d["b"], d["kind"]))
            for c in d["changes"]:
                print("  change:     %s" % c)
            for r in d["regressions"]:
                print("  REGRESSION: %s" % r)
            if not d["changes"] and not d["regressions"]:
                print("  identical fusion inventory")
        return 1 if d.get("regressed") else 0

    if os.path.isdir(args.path):
        reps = reports_on_disk(args.path)
        if args.json:
            print(json.dumps({fp: summary(r) for fp, r in reps.items()},
                             sort_keys=True, default=str))
        else:
            print("%d report(s) in %s" % (len(reps), args.path))
            for fp, rep in reps.items():
                t = rep.get("totals", {})
                print("  %s  %-16s %3d fusions  %.3g bytes%s" % (
                    fp, rep.get("kind"), t.get("fusions", 0),
                    t.get("bytes", 0),
                    "  UNAVAILABLE" if rep.get("unavailable") else ""))
        return 0

    rep = load_report(args.path)
    if rep is None:
        print("cannot load %r (missing or corrupt)" % args.path)
        return 2
    print(json.dumps(rep, sort_keys=True, default=str) if args.json
          else _fmt_report(rep))
    return 0


def reset():
    """Drop captured reports and runtime overrides (test isolation)."""
    global _enabled_override, _dir_override
    with _lock:
        _reports.clear()
    _enabled_override = None
    _dir_override = None


if __name__ == "__main__":
    import sys
    sys.exit(main())
