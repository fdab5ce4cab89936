"""Typed environment-variable registry (reference: the ~85 documented
MXNET_* vars read via dmlc::GetEnv at point of use + the env_var.md doc
page; per-var typed, self-documenting fields like dmlc::Parameter).

Every knob the framework reads from the environment is declared here with
type, default, and documentation. `mx.env.doc()` renders the env_var.md
analog; `mx.runtime.feature_list()` complements this with build/runtime
features. Reference-era MXNET_* names that have a TPU-native counterpart
are registered under BOTH spellings so ported launch scripts keep working.
"""
from __future__ import annotations

import os

__all__ = ["EnvVar", "register", "get", "all_vars", "doc"]

_REGISTRY = {}


class EnvVar:
    def __init__(self, name, type_, default, help_, aliases=()):
        self.name = name
        self.type = type_
        self.default = default
        self.help = help_
        self.aliases = tuple(aliases)

    def read(self):
        for n in (self.name, *self.aliases):
            raw = os.environ.get(n)
            if raw is not None:
                if self.type is bool:
                    return raw.lower() not in ("", "0", "false", "off")
                return self.type(raw)
        return self.default


def register(name, type_, default, help_, aliases=()):
    v = EnvVar(name, type_, default, help_, aliases)
    _REGISTRY[name] = v
    return v


def get(name):
    """Read an env var through its registry entry (typed, with default)."""
    return _REGISTRY[name].read()


def all_vars():
    return dict(_REGISTRY)


def doc():
    """Render the env-var documentation (the env_var.md analog)."""
    lines = ["# Environment variables", ""]
    for v in sorted(_REGISTRY.values(), key=lambda v: v.name):
        alias = f" (aliases: {', '.join(v.aliases)})" if v.aliases else ""
        lines.append(f"* `{v.name}`{alias} — {v.help} "
                     f"(type: {v.type.__name__}, default: {v.default!r})")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# the knob corpus
# ---------------------------------------------------------------------------

register(
    "MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
    "Dependency-engine implementation: ThreadedEnginePerDevice (async, the "
    "default) or NaiveEngine (synchronous — deterministic repro/debugging; "
    "reference: src/engine/engine.cc:32).")
register(
    "MXTPU_DISABLE_NATIVE", bool, False,
    "Disable the native C++ runtime (engine/storage/RecordIO/pipeline) and "
    "fall back to pure-python equivalents.")
register(
    "MXNET_CPU_WORKER_NTHREADS", int, 1,
    "Default host worker-thread count hint for the native pipeline "
    "(reference: threaded_engine_perdevice.cc:98).")
register(
    "MXNET_EXEC_BULK_EXEC_TRAIN", bool, True,
    "Parity no-op: XLA fuses whole programs — bulking has no separate "
    "switch (reference: bulking env family).")
register(
    "MXNET_ENFORCE_DETERMINISM", bool, False,
    "Prefer deterministic lowering (maps to XLA deterministic reductions "
    "where available; RNG is always counter-based/deterministic).")
register(
    "MXNET_SAFE_ACCUMULATION", bool, True,
    "Accumulate bf16 reductions in fp32 (the framework always does this "
    "on TPU; exposed for reference parity).")
register(
    "MXNET_KVSTORE_BIGARRAY_BOUND", int, 1 << 20,
    "Parity knob: arrays above this element count prefer sharded "
    "(reduce-scatter) allreduce in tpu_dist.")
register(
    "MXTPU_FLASH_ATTENTION", bool, True,
    "Use the Pallas flash-attention kernel inside MultiHeadAttention on "
    "TPU (fused QK^T/softmax/PV, O(S) memory). Off-TPU the jnp reference "
    "runs either way.")
register(
    "MXNET_GPU_MEM_POOL_TYPE", str, "Naive",
    "Parity no-op on TPU: device memory pooling is PJRT's "
    "(reference: pooled_storage_manager.h buckets).")
register(
    "MXTPU_IO_WORKER_NTHREADS", int, 2,
    "Native-runtime IO worker threads (checkpoint writes, RecordIO "
    "prefetch; reference: the IO-priority pool of "
    "threaded_engine_perdevice.cc). Read by native/mxtpu_runtime.cc "
    "when the engine starts.")
register(
    "MXTPU_SERVE_MAX_BATCH", int, 32,
    "serving.InferenceEngine default max micro-batch size (top of the "
    "bucket ladder; docs/serving.md).")
register(
    "MXTPU_SERVE_QUEUE", int, 256,
    "serving.InferenceEngine default admission-queue bound; submits "
    "beyond it shed deterministically with serving.Overloaded.")
register(
    "MXTPU_SERVE_MAX_WAIT_MS", float, 2.0,
    "serving.InferenceEngine default batching deadline: a partial batch "
    "launches once its oldest request has waited this long.")
register(
    "MXTPU_SERVE_TIMEOUT_MS", float, 1000.0,
    "serving.InferenceEngine default per-request deadline; requests "
    "not completed in time fail with serving.RequestTimeout.")
register(
    "MXTPU_SERVE_MODE", str, "pipelined",
    "serving.InferenceEngine execution mode: 'pipelined' (assembler + "
    "completer threads, host assembly overlaps device compute) or "
    "'sync' (the serialized PR-3 baseline; docs/serving.md).")
register(
    "MXTPU_SERVE_INFLIGHT", int, 2,
    "serving.InferenceEngine bounded in-flight window: how many "
    "dispatched-but-unsettled micro-batches the assembler may run "
    "ahead (2 = double buffering).")
register(
    "MXTPU_SERVE_DRAIN_MS", float, 10000.0,
    "serving.InferenceEngine.stop(drain=True) default drain bound; the "
    "drain also never outlives the latest queued deadline, and "
    "requests still queued at the bound are force-dropped (counted in "
    "serve_drain_dropped_total).")
register(
    "MXTPU_DECODE_SLOTS", int, 4,
    "decode.DecodeEngine default KV-cache slot count: the fixed "
    "sequence capacity of the paged (num_slots, max_len, ...) pool and "
    "the batch dimension of the steady-state decode step "
    "(docs/decode.md).")
register(
    "MXTPU_DECODE_MAX_LEN", int, 128,
    "decode.DecodeEngine default per-slot context window: prompt + "
    "generated tokens per sequence are capped here (a sequence filling "
    "its slot row retires with reason 'context_full').")
register(
    "MXTPU_DECODE_PREFILL_BUCKETS", str, "",
    "decode.DecodeEngine prefill seq-len bucket ladder as a "
    "comma-separated rung list (e.g. '16,64,128'); empty = the "
    "powers-of-two ladder up to MXTPU_DECODE_MAX_LEN. Every rung is "
    "pre-compiled by warmup(); prompts pad up to the nearest rung.")
register(
    "MXTPU_DECODE_STREAM", bool, True,
    "decode.DecodeEngine streaming default: on, SequenceRequest.stream() "
    "yields each token as its step settles; off, tokens are withheld "
    "until the sequence retires (stream() then yields them in one "
    "burst) — for clients that want whole completions only.")
register(
    "MXTPU_TRACE_SAMPLE", float, 0.0,
    "Head-based request-trace sampling fraction for the serving tier "
    "(observability/reqtrace.py): 0 = off (bit-identical serving path, "
    "zero extra work), 1 = every request, 0.1 = exactly every 10th "
    "(deterministic counter, no RNG). Sampled requests emit phase spans "
    "(admit/queue/assemble/dispatch/device/slice/settle) into the trace "
    "ring, served by opsd GET /traces.")
register(
    "MXTPU_TRACE_RING", int, 1024,
    "Bounded per-process ring of finished request traces "
    "(observability/reqtrace.py); a long-running replica keeps the "
    "newest N traces for /traces and postmortem bundles.")
register(
    "MXTPU_SLO_INTERACTIVE_MS", float, 0.0,
    "Latency objective (ms) for the 'interactive' serving class; 0 "
    "disables SLO tracking for the class. Any class gets an objective "
    "via MXTPU_SLO_<CLASS>_MS (docs/observability.md §6).")
register(
    "MXTPU_SLO_BATCH_MS", float, 0.0,
    "Latency objective (ms) for the 'batch' serving class; 0 disables "
    "SLO tracking for the class.")
register(
    "MXTPU_SLO_TARGET", float, 0.99,
    "SLO success-fraction target: the error budget is 1 - target, and "
    "the serve_slo_burn_rate gauge is the windowed violation fraction "
    "over that budget.")
register(
    "MXTPU_SLO_WINDOW_S", float, 60.0,
    "Rolling window (seconds) SLO burn rates are evaluated over; "
    "violations roll off after this long, which is how a 503'd replica "
    "recovers its /readyz.")
register(
    "MXTPU_SLO_BURN_MAX", float, 1.0,
    "Burn-rate threshold: a class burning hotter than this drops the "
    "replica from opsd /readyz rotation (1.0 = spending the error "
    "budget exactly as fast as the target allows).")
register(
    "MXTPU_SLO_MIN_EVENTS", int, 10,
    "Minimum windowed requests before a class's burn rate can flip "
    "/readyz — keeps one unlucky request from 503ing an idle replica.")
register(
    "MXTPU_FUSED_UPDATE", bool, True,
    "Fused multi-tensor optimizer update: bucket the parameter tree by "
    "(rule, weight dtype, multi-precision) and run ONE donated jit "
    "dispatch per bucket per step, plus the bucketed flat-buffer "
    "allreduce in Trainer.allreduce_grads — collapses O(params) "
    "dispatches to O(buckets). 0 restores the legacy per-parameter "
    "path (docs/performance.md).")
register(
    "MXTPU_FUSED_BUCKET_MB", int, 25,
    "Target flat-buffer size (MB) for the bucketed DDP-style allreduce "
    "in Trainer.allreduce_grads: gradients are concatenated into flat "
    "buffers of roughly this size, one collective dispatch per buffer.")
register(
    "MXTPU_DONATE_UPDATE", bool, True,
    "Donate weight/optimizer-state buffers into optimizer update "
    "dispatches so XLA reuses them in place instead of allocating fresh "
    "HBM. Skipped automatically for any single call where donation "
    "would alias another argument's buffer.")
register(
    "MXTPU_WHOLE_STEP", bool, True,
    "gluon.TrainStep compiled whole-step path: forward + backward + "
    "gradient allreduce + fused optimizer update captured in ONE donated "
    "jit dispatch per training step (params/optimizer state donated, "
    "per-param lr/wd/t as packed vectors — LR schedules never retrace). "
    "0 forces the legacy three-phase record/backward/Trainer.step "
    "sequence; sparse grads, overriding optimizers, clip_global_norm and "
    "multi-copy params fall back automatically (docs/performance.md).")
register(
    "MXTPU_CKPT_ASYNC", bool, True,
    "CheckpointManager default: write+commit checkpoints on an engine IO "
    "thread so saves overlap training (snapshot capture still happens "
    "inline). 0 makes every save synchronous (docs/checkpointing.md).")
register(
    "MXTPU_CKPT_KEEP_LAST", int, 5,
    "CheckpointManager retention: keep the newest N committed "
    "checkpoints, deleting older ones at each commit. 0 disables "
    "deletion.")
register(
    "MXTPU_CKPT_KEEP_EVERY_N", int, 0,
    "CheckpointManager retention: checkpoints whose step is a multiple "
    "of N are milestones kept forever, exempt from KEEP_LAST deletion. "
    "0 disables milestones.")
register(
    "MXTPU_CKPT_VERIFY", bool, True,
    "Verify per-array crc32 checksums against the manifest on restore; "
    "mismatches raise CheckpointCorrupt (latest-checkpoint restores "
    "then fall back to the previous committed step).")
register(
    "MXTPU_CKPT_MODE", str, "replicated",
    "Distributed checkpoint layout: 'replicated' (rank 0 writes the "
    "full state, others barrier) or 'sharded' (each rank persists its "
    "share plus a fragment manifest; rank 0 merges).")
register(
    "MXTPU_CKPT_PREEMPT_SIGNALS", str, "SIGTERM,SIGUSR1",
    "Comma-separated signals the PreemptionHandler intercepts for the "
    "emergency synchronous snapshot.")
register(
    "MXTPU_CKPT_PREEMPT_EXIT_CODE", int, 0,
    "Process exit code after a successful preemption snapshot (0 = "
    "clean shutdown so supervisors treat the job as resumable, not "
    "crashed).")
register(
    "MXTPU_ELASTIC_MAX_RESTARTS", int, 3,
    "Supervisor restart budget (tools/supervisor.py via "
    "elastic.RestartPolicy; docs/elasticity.md): lifetime cap on "
    "restarts after rank deaths before the supervisor gives up and "
    "exits non-zero. -1 = unlimited.")
register(
    "MXTPU_ELASTIC_BACKOFF_S", float, 1.0,
    "Supervisor restart backoff base (seconds): restart N after a rank "
    "death waits base * 2^N, capped at MXTPU_ELASTIC_BACKOFF_MAX_S — "
    "a crash-looping job must not hammer the checkpoint store.")
register(
    "MXTPU_ELASTIC_BACKOFF_MAX_S", float, 30.0,
    "Cap on the supervisor's exponential restart backoff (seconds).")
register(
    "MXTPU_ELASTIC_LR_RESCALE", str, "off",
    "LR rescaling rule when the world size changes at elastic re-entry "
    "(elastic.rescale_lr; docs/elasticity.md): 'off' (default — the "
    "bitwise-safe choice when the GLOBAL batch is held constant across "
    "the migration), 'linear' (lr *= new/old, the Goyal et al. rule "
    "for per-rank batches — global batch shrinks with the world), or "
    "'sqrt' (lr *= sqrt(new/old), the conservative variant). Scheduled "
    "LRs (lr_scheduler) are never touched.")
register(
    "MXTPU_ELASTIC_GENERATION", int, 0,
    "World generation a relaunched rank inherits (stamped by "
    "tools/supervisor.py on every restart): 0 = first launch, +1 per "
    "restart / in-process reenter(). Flows into the flight identity, "
    "opsd /identity, the world_generation gauge, and fleetctl's table.")
register(
    "MXTPU_PASSES", str, "auto",
    "Graph-pass pipeline master switch (mxnet_tpu/passes; "
    "docs/passes.md). 'auto' runs each block's registered passes plus "
    "the env-driven policies; a comma list (e.g. 'amp,numerics') "
    "force-adds those named passes to every pipeline; '0' disables ALL "
    "graph passes so every seam compiles its captured program verbatim "
    "— bitwise-identical to the pre-pipeline framework.")
register(
    "MXTPU_DIAG_COMPILE", bool, True,
    "Capture per-compile cost/memory analysis (flops, peak HBM, compile "
    "seconds) into the diagnostics compile registry at each block-seam "
    "build; 0 skips capture entirely (docs/diagnostics.md).")
register(
    "MXTPU_NUMERICS", str, "off",
    "In-graph numerics checking (observability.numerics; "
    "docs/observability.md): 'step' fuses ONE is-finite AND-reduce over "
    "every inexact program output into each compiled program (verdict "
    "delivered asynchronously, read at the step boundary; a trip "
    "bisects the recorded jaxpr to the first non-finite equation and "
    "raises NonFiniteError with op/shape/operand-stats attribution); "
    "'op' re-emits the program with a per-equation is-finite flag "
    "vector for immediate attribution; 'off' (default) compiles "
    "programs untouched.")
register(
    "MXTPU_FLIGHTREC", bool, True,
    "Flight recorder (observability.flight): append structured runtime "
    "events (steps, compiles, collectives, checkpoint commits, serving "
    "sheds, watchdog beats, numerics trips) to a bounded in-memory "
    "ring for postmortem bundles. 0 reduces recording to a single "
    "branch.")
register(
    "MXTPU_FLIGHTREC_CAPACITY", int, 4096,
    "Flight-recorder ring capacity: the postmortem bundle holds the "
    "LAST this-many events.")
register(
    "MXTPU_FLIGHTREC_DIR", str, ".",
    "Directory postmortem bundles are written to "
    "(mxtpu_blackbox.rank<N>.json, one per rank).")
register(
    "MXTPU_FLIGHTREC_FLUSH_STEPS", int, 0,
    "Spill the postmortem bundle asynchronously every N training-step "
    "events, so a SIGKILL'd run still leaves evidence on disk for "
    "tools/blackbox.py. 0 (default) disables periodic spills; crash "
    "paths (watchdog, preemption, crash hooks, numerics trips) dump "
    "regardless.")
register(
    "MXTPU_FLIGHTREC_CRASHDUMP", bool, False,
    "Auto-install the observability crash hooks at import: sys.excepthook "
    "and atexit write a final postmortem bundle; faulthandler dumps "
    "native-fault tracebacks to a per-rank sidecar file.")
register(
    "MXTPU_JOB_ID", str, "",
    "Job identity stamped into flight-recorder events and span records; "
    "(job_id, step) is the cross-rank trace ID tools/blackbox.py aligns "
    "per-rank postmortem bundles on. Empty = 'local'.")
register(
    "MXTPU_MESH", str, "",
    "Device-mesh axis spec for the sharding subsystem "
    "(mxnet_tpu/sharding; docs/sharding.md), e.g. 'dp=-1' (data "
    "parallel over all devices) or 'dp=4,tp=2'. -1 infers that axis "
    "from the device count. Consulted only when MXTPU_SHARDING=auto "
    "and the Trainer was given no explicit mesh=/sharding_plan=; empty "
    "(default) names no mesh.")
register(
    "MXTPU_SHARDING", str, "auto",
    "Sharding-subsystem mode (mxnet_tpu/sharding; docs/sharding.md): "
    "'off' disables the subsystem entirely — mesh= arguments and "
    "MXTPU_MESH are ignored, the ShardingPass is never injected, and "
    "every code path is bitwise-identical to the unsharded framework; "
    "'auto' (default) builds a plan from explicit Trainer arguments, "
    "else from MXTPU_MESH; 'plan' accepts explicit arguments only "
    "(MXTPU_MESH is ignored, so a launcher's env mesh cannot override "
    "a hand-built plan).")
register(
    "MXTPU_SPEC_LAYOUT", bool, True,
    "SpecLayout rule library for env-driven plans (sharding/layouts.py; "
    "docs/sharding.md): when MXTPU_MESH names the layout's model axes "
    "(fsdp/tp), the resolved plan places stock-block params by "
    "structural role — embeddings, qkv/attention projections, FFN "
    "in/out, norms, conv — over data/fsdp/tp. 0 keeps env meshes "
    "placement-free (axes only, params replicate). Plans built in code "
    "via ShardingPlan.from_layout() carry the library regardless.")
register(
    "MXTPU_ZERO", bool, True,
    "ZeRO optimizer-state sharding (docs/sharding.md): when the plan's "
    "mesh carries the layout's fsdp axis, optimizer state (momentum, "
    "variance, fp32 masters) shards along it on the first unsharded "
    "divisible dim — each rank owns ~1/N of optimizer memory, and the "
    "donated whole-step program reduce-scatters grads / allgathers "
    "updated params in-trace. 0 places state exactly like its weight. "
    "Numerics are identical either way (placement, not math).")
register(
    "MXTPU_OPS_PORT", int, 0,
    "Live ops server (observability.opsd; docs/observability.md): start "
    "a per-process stdlib HTTP server on this port at import, serving "
    "GET /metrics (Prometheus), /healthz, /readyz, /flight, /steps, "
    "/identity and POST /postmortem, /profile?ms=N. 0 (default) creates "
    "no thread or socket. Port 0 is reserved for programmatic "
    "opsd.start(port=0) ephemeral binds (tests).")
register(
    "MXTPU_OPS_HOST", str, "127.0.0.1",
    "Bind address for the live ops server. Loopback by default; set "
    "0.0.0.0 when a fleet supervisor (tools/fleetctl.py) or Prometheus "
    "scrapes ranks across hosts.")
register(
    "MXTPU_OPS_TOKEN", str, "",
    "Optional bearer token for the ops server's mutating POST endpoints "
    "(/postmortem, /profile): when set, requests must carry "
    "'Authorization: Bearer <token>' or get 401. GET endpoints stay "
    "open — they serve the same read-only snapshots a postmortem "
    "bundle contains.")
register(
    "MXTPU_DIAGNOSTICS", bool, True,
    "Diagnostics span recording (diagnostics/spans.py): per-phase "
    "timing records feeding the step table, watchdog, and postmortem "
    "bundles. 0 makes every span a no-op context manager.")
register(
    "MXTPU_DIAG_RING_CAPACITY", int, 4096,
    "Diagnostics span-ring capacity: the per-process ring keeps the "
    "newest N span records for the step table and postmortem bundles.")
register(
    "MXTPU_TELEMETRY", bool, True,
    "Telemetry registry master switch (telemetry/registry.py): 0 turns "
    "every counter/gauge/histogram record into a single-branch no-op "
    "and /metrics serves an empty page.")
