"""Runtime configuration: the MXNET_* environment-variable tier.

Reference: the reference reads ~46 documented env vars via dmlc::GetEnv
at point of use (docs/faq/env_var.md) on top of per-object dmlc
Parameter structs. Here the same tier is a typed registry: every knob
the framework consults is declared once with type/default/doc, read
through :func:`get`, and enumerable for docs (``python -m
mxnet_tpu.config`` prints the table).
"""
from __future__ import annotations

import os

__all__ = ["get", "describe", "VARS"]

# name -> (type, default, doc)
VARS = {
    "MXNET_ENGINE_TYPE": (str, "ThreadedEnginePerDevice",
                          "NaiveEngine = serialize after every op "
                          "(degrade-to-serial debug mode, reference: "
                          "docs/faq/env_var.md:77)."),
    "MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN": (int, 15,
                                            "Engine bulking knob (API "
                                            "parity; XLA fusion subsumes "
                                            "it)."),
    "MXNET_TPU_PS_URI": (str, "", "Parameter-server host for dist_* "
                         "KVStore types (DCN tier)."),
    "MXNET_TPU_PS_PORT": (int, 9090, "Parameter-server port."),
    "MXNET_TPU_PS_BIND": (str, "127.0.0.1", "Server bind address; "
                          "non-loopback requires MXNET_TPU_PS_TOKEN."),
    "MXNET_TPU_PS_TOKEN": (str, "", "Shared auth token for the PS wire "
                           "protocol."),
    "MXNET_TPU_PS_MODE": (str, "sync", "sync = aggregate-then-update "
                          "BSP; async = per-push updates."),
    "MXNET_TPU_NUM_WORKERS": (int, 1, "World size in PS mode."),
    "MXNET_TPU_RANK": (int, 0, "This worker's rank in PS mode."),
    "MXNET_TPU_ROLE": (str, "worker", "PS-mode process role (worker/"
                       "server/scheduler) for the launch.py tooling "
                       "path."),
    "MXNET_DIST_COORDINATOR": (str, "", "host:port of process 0's "
                               "jax.distributed coordinator for "
                               "dist_tpu_sync multi-host training "
                               "(dist_runtime.py). Empty = standard "
                               "cluster autodetection (Cloud TPU / "
                               "SLURM / MPI), or single-process."),
    "MXNET_DIST_NUM_PROCESSES": (int, 1, "World size for the explicit "
                                 "MXNET_DIST_COORDINATOR route."),
    "MXNET_DIST_PROCESS_ID": (int, 0, "This process's rank for the "
                              "explicit MXNET_DIST_COORDINATOR "
                              "route."),
    "MXNET_DIST_DEAD_S": (float, 10.0,
                          "Elastic membership: a dist_tpu_sync rank "
                          "whose control-plane heartbeat is older than "
                          "this is declared lost and a rescale begins "
                          "(elastic.py)."),
    "MXNET_STEP_TIMEOUT_S": (float, 120.0,
                             "Elastic membership: a fused train step "
                             "that has not completed after this long "
                             "is treated as a stalled collective (a "
                             "rank parked in a dead all-reduce) and "
                             "routed to the same rescale path as a "
                             "detected death. 0 disables the "
                             "watchdog."),
    "MXNET_ELASTIC_DIR": (str, "",
                          "Shared directory for the elastic control "
                          "plane (heartbeats, rescale votes/plans, "
                          "join requests). Setting it on a "
                          "dist_tpu_sync fit enables checkpoint-free "
                          "elastic rescale on membership change; "
                          "empty = fail-as-a-unit (PR 4 supervisor "
                          "relaunch)."),
    "MXNET_ELASTIC_HOST": (str, "",
                           "Host this rank advertises in its elastic "
                           "heartbeats (peers dial it when this rank "
                           "becomes the rescale coordinator). Empty = "
                           "127.0.0.1, the single-machine/chaos-test "
                           "default."),
    "MXNET_ELASTIC_HB_S": (float, 1.0,
                           "Elastic membership heartbeat period "
                           "(control-plane file rewrite interval); "
                           "liveness window is MXNET_DIST_DEAD_S."),
    "MXNET_ELASTIC_JOIN": (int, 0,
                           "Set to 1 on a relaunched trainer to enter "
                           "fit in JOIN mode: request admission from "
                           "the running elastic world and adopt its "
                           "plan instead of initializing a new "
                           "cluster (the ProcessSupervisor relaunch "
                           "hook sets this)."),
    "MXNET_KVSTORE_BIGARRAY_BOUND": (int, 1000000,
                                     "Arrays above this size may be "
                                     "sharded across servers "
                                     "(reference: env_var.md:102)."),
    "MXNET_ENFORCE_DETERMINISM": (bool, False,
                                  "Prefer deterministic reductions "
                                  "(maps to XLA deterministic flags)."),
    "MXNET_PROFILER_AUTOSTART": (bool, False,
                                 "Start the profiler at import."),
    "MXNET_TEST_SEED": (int, 0, "RNG seed for the test harness "
                        "(tools/flakiness_checker.py rotates it per "
                        "trial; reference: docs/faq/env_var.md test "
                        "seeding)."),
    "MXNET_UPDATE_BUFFER_DONATION": (bool, True,
                                     "Donate weight/state buffers in "
                                     "optimizer update kernels (XLA "
                                     "input->output aliasing = true "
                                     "in-place updates, no double-"
                                     "buffering)."),
    "MXNET_FUSED_STEP": (bool, True,
                         "Compile forward+backward+optimizer update into "
                         "ONE donated XLA program per train step "
                         "(Executor.train_step; Module/Gluon Trainer "
                         "local-update paths). 0 restores the separate "
                         "forward/vjp programs plus per-parameter update "
                         "dispatches."),
    "MXNET_INT8_CONV_IM2COL": (bool, False,
                               "Force _contrib_quantized_conv_int8 "
                               "through the im2col + Pallas int8-matmul "
                               "route off-TPU too (on TPU it is the "
                               "default). The lax conv path stays the "
                               "bitwise acceptance twin; int32 "
                               "accumulation makes the two routes "
                               "bitwise-identical."),
    "MXNET_TELEMETRY": (bool, True,
                        "Always-on runtime metrics (telemetry.py): op "
                        "dispatch, jit-cache, HBM, kvstore, io "
                        "instruments. 0 removes the hot-path hooks "
                        "entirely; telemetry.enable() flips at runtime."),
    "MXNET_SERVE_MAX_BATCH": (int, 8,
                              "Largest serving batch bucket "
                              "(serve.InferenceEngine). Buckets default "
                              "to the power-of-two ladder 1..max; the "
                              "jit cache holds at most len(buckets) "
                              "forward programs."),
    "MXNET_SERVE_BUCKETS": (str, "", "Explicit serving batch buckets as "
                            "a comma list (e.g. '1,4,16'); empty = "
                            "power-of-two ladder up to "
                            "MXNET_SERVE_MAX_BATCH."),
    "MXNET_SERVE_QUEUE_DEPTH": (int, 64,
                                "Serve admission-control bound: requests "
                                "beyond this many queued are rejected "
                                "immediately (HTTP 503), never queued "
                                "into unbounded latency."),
    "MXNET_SERVE_BATCH_WAIT_MS": (int, 2,
                                  "How long the micro-batcher holds the "
                                  "first queued request open for "
                                  "coalescing (higher = bigger batches, "
                                  "more latency floor)."),
    "MXNET_SERVE_DEADLINE_MS": (int, 2000,
                                "Default per-request serving deadline; "
                                "expired requests fail with HTTP 504 "
                                "before wasting a chip dispatch. "
                                "0 disables."),
    "MXNET_SERVE_WORKERS": (int, 1,
                            "Serve worker threads pulling batches off "
                            "the queue. >1 overlaps host pad/unpad and "
                            "JSON work with device compute (per-bucket "
                            "executors are lock-guarded)."),
    "MXNET_SERVE_WORKER_RESTARTS": (int, 16,
                                    "Restart budget for crashed serve "
                                    "worker threads (shared across the "
                                    "crew, counted in serving/"
                                    "worker_restarts_total). Past it a "
                                    "crashed worker stays down; with no "
                                    "worker alive /healthz degrades to "
                                    "not-ready."),
    "MXNET_SERVE_SHADOW_FRACTION": (float, 0.0,
                                    "Default fraction of live requests "
                                    "ModelRegistry.enable_shadow mirrors "
                                    "to the shadow (quantized) engine "
                                    "for drift measurement "
                                    "(quantize/shadow_drift). Mirrors "
                                    "run on a side thread and never "
                                    "delay or fail primary requests."),
    "MXNET_FLEET_MIN_REPLICAS": (int, 1,
                                 "Fleet tier lower bound: the autoscaler "
                                 "never retires below this many live "
                                 "replicas (serve.fleet)."),
    "MXNET_FLEET_MAX_REPLICAS": (int, 4,
                                 "Fleet tier upper bound: the autoscaler "
                                 "never spawns past this many replicas."),
    "MXNET_FLEET_PREFIX_TOKENS": (int, 16,
                                  "Prompt-head length the router hashes "
                                  "for /generate prefix affinity: "
                                  "requests sharing their first N "
                                  "tokens pin to one replica's KV/"
                                  "prefix-cache locality domain."),
    "MXNET_FLEET_AFFINITY_SLACK": (int, 4,
                                   "Affinity yields to load: when the "
                                   "pinned replica carries this many "
                                   "more outstanding requests than the "
                                   "least-loaded one, the router "
                                   "breaks affinity for the request "
                                   "(router/affinity_yields_total)."),
    "MXNET_FLEET_FORWARD_RETRIES": (int, 2,
                                    "Router forward retries across "
                                    "OTHER replicas after a connection "
                                    "failure ejects the picked one "
                                    "(only before any response byte "
                                    "reached the client)."),
    "MXNET_FLEET_SCALE_UP_S": (float, 10.0,
                               "Autoscaler hold window: the hot signal "
                               "(replica SLO burn on /alerts, or queue "
                               "depth past MXNET_FLEET_QUEUE_UP) must "
                               "be sustained this long before a "
                               "scale-up."),
    "MXNET_FLEET_SCALE_DOWN_S": (float, 30.0,
                                 "Autoscaler hold window: fleet-wide "
                                 "slack (no burn, queues under "
                                 "MXNET_FLEET_QUEUE_DOWN) must be "
                                 "sustained this long before a "
                                 "scale-down (hysteresis against "
                                 "flapping; > MXNET_FLEET_SCALE_UP_S "
                                 "by design)."),
    "MXNET_FLEET_COOLDOWN_S": (float, 15.0,
                               "Minimum wall between autoscaler "
                               "actions — a fresh replica gets to "
                               "absorb load before the next verdict."),
    "MXNET_FLEET_INTERVAL_S": (float, 1.0,
                               "Autoscaler control-loop tick: how often "
                               "replica /alerts + queue signals are "
                               "polled."),
    "MXNET_FLEET_QUEUE_UP": (float, 4.0,
                             "Mean per-replica serving/queue_depth "
                             "above which a tick reads hot (queue "
                             "growth scales up before the burn-rate "
                             "windows mature)."),
    "MXNET_FLEET_QUEUE_DOWN": (float, 0.5,
                               "Max per-replica serving/queue_depth "
                               "below which (absent burn) a tick reads "
                               "cold."),
    "MXNET_FLEET_SPAWN_TIMEOUT_S": (float, 120.0,
                                    "Spawn-to-ready budget: a replica "
                                    "that has not passed /healthz by "
                                    "then is killed and triaged as a "
                                    "failure."),
    "MXNET_FLEET_DRAIN_TIMEOUT_S": (float, 30.0,
                                    "Retirement drain budget: how long "
                                    "a quiesced replica may take to "
                                    "finish its outstanding requests "
                                    "before SIGTERM regardless."),
    "MXNET_QUANT_PERCENTILE": (float, 99.99,
                               "Percentile of |x| the percentile/"
                               "entropy calibration observer clips "
                               "activation ranges at "
                               "(quantize.calibrate."
                               "PercentileObserver) — outliers stop "
                               "stretching every other value's int8 "
                               "resolution."),
    "MXNET_DECODE_SLOTS": (int, 8,
                           "Concurrent sequences the decode engine "
                           "(serve.DecodeEngine) schedules per step. "
                           "Decode compiles one program per power-of-"
                           "two slot bucket up to this."),
    "MXNET_DECODE_PAGE_SIZE": (int, 16,
                               "Tokens per KV-cache page. Smaller = "
                               "less reserved-memory waste per "
                               "sequence, more block-table gather "
                               "entries per step."),
    "MXNET_DECODE_NUM_PAGES": (int, 512,
                               "KV-cache page pool size (page 0 is a "
                               "reserved null page). HBM cost: 2 * "
                               "layers * pages * page_size * kv_heads "
                               "* head_dim * itemsize. Admission "
                               "refuses requests the free list cannot "
                               "cover (503, page-exhaustion detail)."),
    "MXNET_DECODE_MAX_CONTEXT": (int, 256,
                                 "Max prompt + generated tokens per "
                                 "sequence (must be a multiple of the "
                                 "page size; sets the block-table "
                                 "width and the prefill ladder top)."),
    "MXNET_DECODE_QUEUE_DEPTH": (int, 64,
                                 "Decode admission bound: requests "
                                 "waiting for a slot beyond this are "
                                 "rejected immediately (HTTP 503)."),
    "MXNET_DECODE_MAX_NEW_TOKENS": (int, 128,
                                    "Default and cap for a request's "
                                    "max_new_tokens (bounds its page "
                                    "reservation)."),
    "MXNET_DECODE_DEADLINE_MS": (int, 30000,
                                 "Default per-request decode deadline "
                                 "(queued or mid-stream; expired "
                                 "sessions are retired and their "
                                 "pages freed). 0 disables."),
    "MXNET_CKPT_GRACE_S": (int, 30,
                           "Preemption grace window: on SIGTERM, fit "
                           "finishes the in-flight batch and takes a "
                           "final checkpoint; a watchdog hard-exits the "
                           "process when the window ends (the platform "
                           "reclaims the VM then anyway). 0 disables "
                           "the watchdog."),
    "MXNET_KV_RETRIES": (int, 4,
                         "Max retries per kvstore op after a transient "
                         "transport failure (jittered exponential "
                         "backoff; kvstore/retries_total counts them). "
                         "Exhaustion raises a clear MXNetError naming "
                         "the op and attempt count."),
    "MXNET_KV_TIMEOUT_MS": (int, 60000,
                            "Per-op kvstore deadline: bounds each "
                            "socket wait AND the total retry budget, "
                            "so a dead parameter server degrades to an "
                            "error, never a hang. 0 = no deadline."),
    "MXNET_KV_BACKOFF_MS": (int, 50,
                            "Base kvstore retry backoff; attempt n "
                            "sleeps ~base*2^(n-1) with full jitter, "
                            "capped by the remaining op deadline."),
    "MXNET_KV_DEAD_S": (float, 60.0,
                        "Liveness timeout for PS-mode workers: a rank "
                        "with no traffic (RPCs or heartbeats) for this "
                        "many seconds is declared dead. dist_sync rounds "
                        "and barriers then FAIL FAST with an MXNetError "
                        "naming the dead rank(s) instead of hanging; "
                        "dist_async membership just shrinks until the "
                        "rank rejoins. Clients heartbeat at a third of "
                        "this interval."),
    "MXNET_KV_SNAPSHOT_PATH": (str, "",
                               "KVStore server state snapshot file "
                               "(store, barrier generation, RPC dedup "
                               "commit records, membership epochs, "
                               "server-side optimizer state). Empty "
                               "disables snapshots; set it to make the "
                               "server restartable with --restore after "
                               "a SIGKILL."),
    "MXNET_KV_SNAPSHOT_S": (float, 10.0,
                            "Async-mode snapshot throttle: at most one "
                            "server snapshot per this many seconds "
                            "(updates applied since the last snapshot "
                            "are the documented failover loss window). "
                            "Sync mode ignores it — every committed "
                            "round snapshots before acking, so a "
                            "restored sync run is bitwise-identical."),
    "MXNET_SUPERVISOR_MAX_FAILURES": (int, 3,
                                      "TrainingSupervisor.supervise "
                                      "stop-bound for GENUINE failures "
                                      "(nonzero exit from an uncaught "
                                      "exception). Preemption-grade "
                                      "deaths (signal kills, rc 137/"
                                      "143) relaunch without burning "
                                      "this budget."),
    "MXNET_TRACING": (bool, True,
                      "End-to-end span tracing (tracing.py): request/"
                      "step timelines propagated across serve, "
                      "executor, kvstore, and module layers. 0 removes "
                      "every call-site hook (one module-bool check, "
                      "like fault.py)."),
    "MXNET_TRACE_SAMPLE": (float, 1.0,
                           "Head-sampling probability for new traces "
                           "(decided once at the root: an HTTP request "
                           "or a train step). 0 disables recording but "
                           "keeps X-Request-Id echo; lower in "
                           "production to bound tracer work."),
    "MXNET_TRACE_SLOW_MS": (int, 1000,
                            "Slow-exemplar threshold: sampled traces "
                            "whose root span exceeds this many ms (and "
                            "every sampled trace ending in an error/"
                            "timeout/injected fault) are retained in a "
                            "separate always-kept ring."),
    "MXNET_TRACE_RING": (int, 64,
                         "How many finished traces the in-memory ring "
                         "keeps for /traces and the chrome-trace "
                         "merge."),
    "MXNET_LOG_JSON": (bool, False,
                       "log.get_logger emits one JSON object per "
                       "record (ts/level/name/msg + trace_id/span_id "
                       "from the active trace context). 0 keeps the "
                       "plain formatter, which appends [trace=…] when "
                       "a context is active."),
    "MXNET_NUMERICS": (str, "off",
                       "In-program numerics sentinels folded into the "
                       "fused train step (health.py): off | step "
                       "(loss proxy + global grad norm + nonfinite "
                       "count, one small D2H fetch per step) | full "
                       "(adds per-parameter attribution so a trip "
                       "names the layer). Zero extra host dispatches, "
                       "zero recompiles across LR steps."),
    "MXNET_NUMERICS_POLICY": (str, "warn",
                              "What a numerics-sentinel trip does: "
                              "warn (log + count + flight-record, "
                              "keep training) | raise "
                              "(health.NumericsError) | "
                              "checkpoint-and-raise (fit saves the "
                              "tripped state under <prefix>.numerics "
                              "for forensics, then raises)."),
    "MXNET_NUMERICS_SPIKE": (float, 0.0,
                             "Grad-norm spike threshold: trip when the "
                             "global grad norm exceeds this many times "
                             "its running EMA. 0 disables spike "
                             "detection (nonfinite detection stays "
                             "on)."),
    "MXNET_FLIGHT_RECORDER": (str, "",
                              "Crash-safe flight-recorder path "
                              "(blackbox.py): lifecycle events "
                              "(compiles, swaps, failovers, rejoins, "
                              "checkpoints, faults, alerts, numerics "
                              "trips) appended as CRC-framed fsync'd "
                              "records readable post-mortem via "
                              "python -m mxnet_tpu.blackbox. Empty "
                              "disables."),
    "MXNET_FLIGHT_RECORDER_MB": (float, 4.0,
                                 "Flight-recorder ring bound: the "
                                 "active segment rotates to <path>.1 "
                                 "at half this size, so on-disk "
                                 "footprint never exceeds ~this many "
                                 "MB and the newest events always "
                                 "survive."),
    "MXNET_SLO_INTERVAL_S": (float, 2.0,
                             "SLO evaluator wake period (health.py "
                             "background thread; it only READS "
                             "telemetry). Rules fire on multi-window "
                             "burn rate, so the interval bounds "
                             "detection latency, not sensitivity."),
    "MXNET_SLO_SERVE_P99_MS": (float, 1000.0,
                               "Default serve_p99 SLO rule threshold: "
                               "interval-local p99 of serving/"
                               "request_seconds above this fires "
                               "/alerts after the burn windows "
                               "agree."),
    "MXNET_SLO_DECODE_ITL_P99_MS": (float, 250.0,
                                    "Default decode_itl_p99 SLO rule "
                                    "threshold over decode/"
                                    "step_seconds p99 (inter-token "
                                    "latency)."),
    "MXNET_SLO_BADPUT_FRACTION": (float, 0.5,
                                  "Default badput_fraction SLO rule "
                                  "threshold on the goodput/"
                                  "badput_fraction gauge: the fraction "
                                  "of run wall NOT spent in useful "
                                  "training-step compute sustained "
                                  "above this fires /alerts."),
    "MXNET_GOODPUT": (bool, True,
                      "Training goodput ledger (goodput.py): "
                      "attribute every wall-second of a fit to one "
                      "category (step_compute/data_wait/compile/"
                      "checkpoint/rescale/restart/straggler_wait/"
                      "idle). Pure host arithmetic, zero extra device "
                      "dispatches; 0 removes the fit-loop hooks."),
    "MXNET_GOODPUT_PREV_EXIT_TS": (str, "",
                                   "Unix timestamp of the supervised "
                                   "predecessor process's death, "
                                   "stamped into a relaunched child's "
                                   "env by checkpoint."
                                   "ProcessSupervisor.run so the "
                                   "child's goodput ledger books the "
                                   "relaunch gap as `restart`. Not "
                                   "set by hand."),
    "MXNET_OBSERVATORY_TIMEOUT_S": (float, 2.0,
                                    "Per-peer HTTP timeout of the "
                                    "cluster observatory's read-only "
                                    "scrapes (observatory.py); a peer "
                                    "that cannot answer within it "
                                    "counts one observatory/"
                                    "scrape_failures_total and is "
                                    "skipped, never raised."),
    "MXNET_FORENSICS": (int, 0,
                        "Compiler-forensics capture (forensics.py): "
                        "after health.capture_cost registers a "
                        "program, also capture its optimized HLO "
                        "(AOT lower+compile under "
                        "suppress_compile_tracking — a persistent-"
                        "cache disk load) and write the per-fusion "
                        "report artifact. Once per program, nothing "
                        "per step."),
    "MXNET_FORENSICS_DIR": (str, "",
                            "Forensics report directory (CRC'd "
                            "<fingerprint>.json artifacts, atomic "
                            "writes). Empty: defaults to "
                            "<compile cache dir>/forensics "
                            "(programs.cache_dir())."),
    "MXNET_PROGRAMS_MAX": (int, 512,
                           "Compiled-program registry bound "
                           "(programs.get_or_build): past this many "
                           "entries the least-recently-used is evicted "
                           "(programs/evictions_total counts them). "
                           "0 = unbounded."),
    "MXNET_FAULT_INJECT": (str, "",
                           "Arm fault-injection points at import: "
                           "point:step:kind[:count] comma list "
                           "(kinds: raise/transient/delay/crash; see "
                           "mxnet_tpu/fault.py). Test-only — never set "
                           "in production."),
    "MXNET_IO_WORKERS": (int, 0,
                         "Decode worker processes for io.DataPipeline. "
                         "0 = inline decode on the staging thread "
                         "(bitwise-identical stream, no parallelism); "
                         "-1 = host cores minus one. Production TPU VMs "
                         "want this near the host core count."),
    "MXNET_IO_PREFETCH": (int, 2,
                          "Depth of the DataPipeline device staging "
                          "buffer: how many decoded batches are "
                          "device_put ahead of the consumer so H2D "
                          "overlaps the previous step's compute. Also "
                          "bounds in-flight decode (workers + prefetch) "
                          "— the pipeline's backpressure."),
    "MXNET_IO_WORKER_RESTARTS": (int, 4,
                                 "Restart budget for crashed "
                                 "DataPipeline decode workers "
                                 "(io/worker_restarts_total counts "
                                 "them). In-flight batches are "
                                 "re-decoded on restart; past the "
                                 "budget the pipeline raises instead "
                                 "of looping a crashing worker."),
    "MXNET_DATALOADER_START_METHOD": (str, "fork",
                                      "Process start method for "
                                      "DataLoader AND io.DataPipeline "
                                      "workers (fork/spawn/forkserver). "
                                      "fork shares the dataset/source "
                                      "copy-on-write but inherits JAX's "
                                      "threads; use spawn/forkserver if "
                                      "forked workers crash (script "
                                      "then needs the standard __main__ "
                                      "guard)."),
}


def get(name, default=None):
    """Read a declared config var with its registered type/default."""
    if name in VARS:
        typ, reg_default, _ = VARS[name]
        raw = os.environ.get(name)
        if raw is None:
            return reg_default if default is None else default
        if typ is bool:
            return raw.lower() in ("1", "true", "yes", "on")
        return typ(raw)
    return os.environ.get(name, default)


def describe():
    """Human-readable table of every config variable."""
    lines = []
    for name in sorted(VARS):
        typ, default, doc = VARS[name]
        cur = os.environ.get(name)
        lines.append("%-40s %-6s default=%-24r %s%s" %
                     (name, typ.__name__, default,
                      ("[set: %r] " % cur) if cur is not None else "", doc))
    return "\n".join(lines)


if __name__ == "__main__":
    print(describe())
