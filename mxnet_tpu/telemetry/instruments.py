"""The framework's metric catalog + the record_* helpers hot paths call.

Every instrumentation touchpoint in the framework goes through ONE helper
here (record_compile / record_fallback / record_transfer / record_sync /
record_collective / observe_step), so:

  * the catalog below is the single source of metric names, labels, and
    buckets (docs/telemetry.md mirrors it);
  * call sites stay one line;
  * the disabled path is a single `REGISTRY.enabled` check before any
    lock, float math, or label resolution.

Metric names follow Prometheus conventions (`_total` counters, `_seconds`
base units), unprefixed — one process, one framework.
"""
from __future__ import annotations

import threading
import time

from ..diagnostics import spans as _spans
from .registry import REGISTRY, counter, gauge, histogram

__all__ = [
    "jit_compile_total", "jit_compile_seconds", "jit_trace_total",
    "hybridize_fallback_total", "attention_kernel_fallback_total",
    "record_attention_fallback",
    "attention_maskfree_share", "set_attention_maskfree_share",
    "attention_fused_backward_share", "record_attention_backward_plan",
    "attention_pairs_visited", "attention_pairs_kept", "set_attention_pairs",
    "qk_prep_kernel_share", "record_qk_prep_site",
    "mla_heads_kernel_share", "record_mla_heads_site",
    "looped_stack_copies", "ut_steps", "set_looped_stack", "exit_mass",
    "stage_exit_mass", "flush_exit_mass",
    "kda_scan_calls", "kda_scan_chunks", "kda_scan_kept_bytes",
    "record_kda_scan",
    "short_conv_sites", "decoder_layers", "record_short_conv_site",
    "short_conv_sites_traced", "set_decoder_stack",
    "xla_compile_seconds_total", "xla_programs_total",
    "install_compile_listener",
    "transfer_total", "transfer_bytes_total",
    "sync_total", "sync_blocked_seconds_total",
    "collective_total", "collective_bytes_total",
    "collective_seconds_total",
    "step_total", "step_time_seconds", "examples_per_second",
    "mfu_ratio", "flops_per_step", "peak_flops",
    "update_dispatch_total", "fused_bucket_size", "update_donated_bytes",
    "record_update_dispatch", "record_fused_bucket",
    "step_dispatch_total", "step_donated_bytes",
    "step_scalar_operands", "record_step_scalar_operands",
    "moe_rows_routed_here", "moe_expert_load_max_over_mean",
    "moe_buffer_rows", "moe_bias_moved_share", "stage_moe_load",
    "flush_moe_load",
    "pass_applied_total", "pass_rewrite_ms", "record_pass",
    "data_prefetch_total", "data_prefetch_depth",
    "record_step_dispatch", "record_device_prefetch",
    "compile_flops", "compile_peak_hbm_bytes", "device_memory_bytes",
    "ckpt_save_total", "ckpt_save_ms", "ckpt_bytes_total",
    "ckpt_restore_total", "record_ckpt_save", "record_ckpt_restore",
    "serve_request_total", "serve_request_latency_seconds",
    "serve_queue_depth", "serve_in_flight",
    "serve_batch_total", "serve_batch_size", "serve_padded_rows_total",
    "serve_shed_total", "serve_timeout_total",
    "serve_dispatch_total", "serve_inflight_batches",
    "serve_class_queue_depth", "serve_class_shed_total",
    "serve_drain_dropped_total",
    "serve_trace_total", "serve_slo_burn_rate",
    "serve_slo_violation_total",
    "decode_tokens_total", "decode_sequence_total",
    "decode_slot_occupancy", "decode_prefill_ms", "decode_step_ms",
    "decode_ttft_ms",
    "record_decode_prefill", "record_decode_step",
    "record_decode_tokens", "record_decode_retire",
    "set_decode_occupancy",
    "record_compile", "record_trace", "record_fallback", "record_transfer",
    "record_sync", "record_collective", "observe_step", "set_flop_budget",
    "record_serve_request", "record_serve_batch", "record_serve_trace",
    "set_slo_burn", "record_slo_violation", "nbytes_of",
    "numerics_trip_total", "flight_events_total", "postmortem_dump_total",
    "record_numerics_trip", "record_flight_event", "record_postmortem",
    "sharding_plan_applied_total", "sharding_mesh_axis_size",
    "sharding_pass_stamp_total",
    "record_sharding_apply", "record_sharding_stamp",
    "elastic_restart_total", "reshard_ms", "world_generation",
    "record_elastic_restart", "record_reshard", "set_world_generation",
    "DEVICE_PEAKS", "device_peaks",
]

# Published per-chip peaks, keyed by jax's `device_kind`: the MFU
# gauge's denominator (set_flop_budget).  A device that is not listed
# has no peak: device_peaks() raises for it.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' (197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip)",
    },
}


def device_peaks(device_kind=None):
    """The published peaks of `device_kind` (default: this process's
    first device).  KeyError for a device the table does not list — an
    unknown chip has no assumed peak."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}") from None

_COMPILE_BUCKETS = (.01, .05, .1, .25, .5, 1.0, 2.5, 5.0, 10.0, 30.0,
                    60.0, 120.0, 300.0)
_STEP_BUCKETS = (.001, .0025, .005, .01, .025, .05, .1, .25, .5,
                 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
_SYNC_BUCKETS = (.0001, .001, .01, .1, 1.0, 10.0)  # noqa: F841 (doc aid)
_SERVE_LATENCY_BUCKETS = (.0005, .001, .0025, .005, .01, .025, .05, .1,
                          .25, .5, 1.0, 2.5, 5.0, 10.0)
_SERVE_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)
_FUSED_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
_CKPT_MS_BUCKETS = (1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
                    1000.0, 2500.0, 5000.0, 10000.0, 30000.0)
_PASS_MS_BUCKETS = (.1, .5, 1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                    500.0, 1000.0, 5000.0)
_DECODE_MS_BUCKETS = (.05, .1, .25, .5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                      100.0, 250.0, 500.0, 1000.0, 2500.0)

# -- compiles ---------------------------------------------------------------
jit_compile_total = counter(
    "jit_compile_total",
    "CachedOp variant builds: trace + XLA compile + first run "
    "(gluon/block.py _call_cached cache miss)", ["block", "variant"])
jit_compile_seconds = histogram(
    "jit_compile_seconds",
    "Wall time of each CachedOp variant build (trace+compile+first run)",
    ["block", "variant"], buckets=_COMPILE_BUCKETS)
jit_trace_total = counter(
    "jit_trace_total",
    "jit retraces per block variant: one per new input signature — each "
    "is one XLA compile, including shape-cache misses AFTER the variant "
    "was first built (gluon/block.py cached_fn; the serving warmup "
    "zero-miss proof reads the per-block counterpart)", ["block", "variant"])
# what JAX itself compiles, under every jit of the package and of the
# user: fed by ONE jax.monitoring listener (install_compile_listener)
xla_compile_seconds_total = counter(
    "xla_compile_seconds_total",
    "Seconds JAX spent per compile stage, summed over every program of "
    "the process (per program and in time: the span ring's xla.<stage> "
    "records, diagnostics.startup_report()): trace (jaxpr), lower (to "
    "MLIR), backend (XLA compile OR the persistent-cache lookup and "
    "load — JAX times both under one event), cache_load (the load "
    "alone; backend - cache_load is what XLA spent building)", ["stage"])
xla_programs_total = counter(
    "xla_programs_total",
    "Programs JAX obtained an executable for: how=loaded from the "
    "persistent compile cache, how=built by XLA", ["how"])
hybridize_fallback_total = counter(
    "hybridize_fallback_total",
    "Hybridized blocks that fell back to imperative execution on a "
    "dynamic-output op (gluon/block.py)", ["block"])
attention_kernel_fallback_total = counter(
    "attention_kernel_fallback_total",
    "flash_attention calls that asked for the kernel and ran the plain "
    "reference because the kernel cannot tile them: reason=width (a head "
    "width that is not a multiple of 8), reason=tile (blocks that do not "
    "divide the padded length)", ["reason"])
attention_maskfree_share = gauge(
    "attention_maskfree_share",
    "Of the sub-tiles of scores a flash kernel's span schedule visits, the "
    "share that takes the mask-free body (every pair kept: no codes read, "
    "no compare, no select); 1 is an unmasked call. Set on the host when "
    "the plan of a signature is built (ops.pallas_attention._plan), from "
    "the schedule's class bits; the latest signature's. kernel is "
    "flash_attention_fwd and, by the backward's memory plan, either "
    "flash_attention_bwd (fused) or flash_attention_bwd_dq and "
    "flash_attention_bwd_dkv", ["kernel"])
attention_fused_backward_share = gauge(
    "attention_fused_backward_share",
    "Of the flash_attention signatures planned so far, the share whose "
    "backward is the one fused kernel (dQ, dK and dV from one pass over "
    "the scores: a head's keys, values and their float32 gradients fit "
    "the stated share of the core's fast memory); a signature whose "
    "backward is the dQ and the dK/dV kernel counts as 0. Set on the host "
    "when the plan of a signature is built (ops.pallas_attention._plan)")
attention_stat_bytes_per_row = gauge(
    "attention_stat_bytes_per_row",
    "HBM bytes a query row's per-row statistics of the flash kernels (the "
    "logsumexp the forward saves; delta = rowsum(dO . O) is made inside "
    "the backward's kernels and is never there) occupy between the "
    "kernels, under the (8, 128) tiling of the stored form's last two "
    "dimensions: 4 where a q tile of 1024 rows stores its lse as one dense "
    "(8, 128) tile, 32 at a tile of 128 (1024 where lse and delta were "
    "(heads, S, 1) columns). The latest "
    "signature's; set on the host when its plan is built "
    "(ops.pallas_attention._plan)")
attention_pairs_visited = gauge(
    "attention_pairs_visited",
    "Query-key pairs of one head that the flash forward computes: the "
    "sub-tiles its span schedule visits (every class but dead), less the "
    "dead quarters of the masked ones where the walk cuts them; for the "
    "latest plan of each kind of mask: window, causal, block_diffusion, "
    "padding, none. Over attention_pairs_kept it is the work the kernels "
    "spend per pair the mathematics needs; set on the host when the plan "
    "of a signature is built (ops.pallas_attention._plan)", ["mask"])
attention_pairs_kept = gauge(
    "attention_pairs_kept",
    "Query-key pairs of one head that the static mask keeps, counted from "
    "the mask's codes, for the latest plan of each kind of mask (a band of "
    "W over S positions: W (W + 1) / 2 + (S - W) W); beside "
    "attention_pairs_visited", ["mask"])
qk_prep_kernel_share = gauge(
    "qk_prep_kernel_share",
    "Of the call sites of ops.pallas_qk_prep.rms_norm_rotary traced so far, "
    "the share that took the fused kernels (a TPU, a head width that is a "
    "multiple of 128 or 64-wide heads two to a lane block, a length that "
    "is a multiple of 8); a site on the "
    "composition rms_norm -> rotary_embedding -> transpose counts as 0. "
    "Set on the host each time the op is traced")
mla_heads_kernel_share = gauge(
    "mla_heads_kernel_share",
    "Of the call sites of ops.pallas_mla_heads.mla_heads traced so far, the "
    "share that took the fused kernels (a TPU, nope and v widths that are "
    "multiples of 128, a rope width of 64, an even number of heads, a "
    "length that is a multiple of 8); a site on the composition of XLA ops "
    "(rotary_embedding, broadcast, concatenate, transpose) counts as 0. "
    "Set on the host each time the op is traced")
kda_scan_calls = gauge(
    "kda_scan_calls",
    "Call sites of ops.pallas_kda.kda_scan (Kimi Delta Attention's chunked "
    "scan) traced so far, by the path they took: kernel (the two Pallas "
    "kernels: a TPU, key and value widths that are multiples of 128, a "
    "chunk that is a multiple of 8) or composition (the same chunk "
    "arithmetic as a lax.scan of XLA ops). Set on the host each time the "
    "op is traced", ["path"])
kda_scan_chunks = gauge(
    "kda_scan_chunks",
    "Chunks (batch x heads x ceil(S / chunk)) of the kda_scan call traced "
    "last: the steps of its sequential walk times the states it carries")
kda_scan_kept_bytes = gauge(
    "kda_scan_kept_bytes",
    "Bytes the backward of the kda_scan call traced last keeps beside the "
    "op's five operands: the float32 state that entered each chunk "
    "(chunks x d_k x d_v x 4)")
looped_stack_copies = gauge(
    "looped_stack_copies",
    "Copies of the layer stack that the traced program of a model which "
    "runs its stack several times on shared weights holds: 1 says the "
    "passes are one rolled loop (gluon.model_zoo.decoder.run_looped), the "
    "number of passes that they were unrolled. Set on the host while the "
    "step is traced")
ut_steps = gauge(
    "ut_steps",
    "Passes of the layer stack (loop steps on shared weights) in the "
    "program traced last; set with looped_stack_copies")
exit_mass = gauge(
    "exit_mass",
    "Of a model with an exit after every pass of its looped stack: the "
    "mean over the last step's positions of the probability that the exit "
    "gate gives each loop step (1-based); the steps' values sum to 1. "
    "Produced on the device; set by flush_exit_mass()", ["step"])
short_conv_sites = gauge(
    "short_conv_sites",
    "Call sites of ops.short_conv.gated_short_conv (the gated short "
    "convolution's mix, a token mixer that is not attention) in the decoder "
    "stack traced last: one a layer whose operator is the convolution. Set "
    "on the host while the step is traced, with decoder_layers")
decoder_layers = gauge(
    "decoder_layers",
    "Layers of each kind in the decoder stack traced last, of a model "
    "whose layers choose their token mixer and their feed-forward one by "
    "one (gluon.model_zoo.lfm2_moe): operator is conv or attention, "
    "feed_forward is dense or moe. Set on the host while the step is "
    "traced", ["operator", "feed_forward"])
compile_flops = gauge(
    "compile_flops",
    "XLA cost_analysis flops of the latest executable per block variant "
    "(diagnostics.introspect)", ["block", "variant"])
compile_peak_hbm_bytes = gauge(
    "compile_peak_hbm_bytes",
    "Approx peak HBM of the latest executable per block variant: "
    "arg+output+temp+code bytes from memory_analysis "
    "(diagnostics.introspect)", ["block", "variant"])

# -- host<->device transfers ------------------------------------------------
transfer_total = counter(
    "transfer_total", "Explicit array transfers by direction "
    "(h2d: mx.np.array/creation, d2h: asnumpy, d2d: copyto)",
    ["direction"])
transfer_bytes_total = counter(
    "transfer_bytes_total", "Bytes moved by explicit array transfers",
    ["direction"])
device_memory_bytes = gauge(
    "device_memory_bytes",
    "Live bytes_in_use per device from memory_stats() — None-reporting "
    "backends (CPU) never set this (diagnostics.introspect)", ["device"])

# -- sync points ------------------------------------------------------------
sync_total = counter(
    "sync_total", "Blocking sync points by site (engine.waitall / "
    "engine.wait_to_read)", ["site"])
sync_blocked_seconds_total = counter(
    "sync_blocked_seconds_total",
    "Host wall time spent blocked in sync points", ["site"])

# -- collectives ------------------------------------------------------------
collective_total = counter(
    "collective_total", "Collective dispatches by op (kvstore pushpull/"
    "broadcast, parallel.collectives psum/all_gather/...)", ["op"])
collective_bytes_total = counter(
    "collective_bytes_total", "Input bytes handed to each collective",
    ["op"])
collective_seconds_total = counter(
    "collective_seconds_total",
    "Host wall time in collective dispatch (async: excludes on-device "
    "completion unless the call itself syncs)", ["op"])

# -- training steps ---------------------------------------------------------
step_total = counter(
    "step_total", "Trainer.step calls (optimizer updates dispatched)")
step_time_seconds = histogram(
    "step_time_seconds",
    "Interval between consecutive Trainer.step completions (full "
    "iteration: data + forward + backward + update dispatch)",
    buckets=_STEP_BUCKETS)
examples_per_second = gauge(
    "examples_per_second",
    "batch_size / last step interval (Trainer.step batch_size)")
mfu_ratio = gauge(
    "mfu_ratio", "Model FLOP utilization: declared flops_per_step / "
    "step interval / peak_flops (set_flop_budget)")
flops_per_step = gauge(
    "flops_per_step", "Declared per-step FLOP budget (set_flop_budget)")
peak_flops = gauge(
    "peak_flops", "Declared accelerator peak FLOP/s (set_flop_budget)")

# -- optimizer update dispatch (optimizer/optimizer.py; gluon/trainer.py) ---
update_dispatch_total = counter(
    "update_dispatch_total",
    "Optimizer update jit dispatches by path: fused (one per bucket per "
    "step), fused_norm (global-norm pre-pass), per_param (legacy "
    "fallback), sparse (row_sparse lazy update)", ["path"])
fused_bucket_size = histogram(
    "fused_bucket_size",
    "Parameters packed into each fused dispatch bucket, by site "
    "(update = fused optimizer step, allreduce = flat-buffer collective)",
    ["site"], buckets=_FUSED_BUCKETS)
update_donated_bytes = counter(
    "update_donated_bytes",
    "Bytes of weight/optimizer-state buffers donated into update "
    "dispatches — XLA reuses them in place instead of allocating fresh "
    "HBM for the outputs")

# -- whole-step compiled path (gluon/train_step.py; docs/performance.md) ----
step_dispatch_total = counter(
    "step_dispatch_total",
    "Training-step executions by path: whole_step (ONE donated jit "
    "dispatch covering forward + backward + allreduce + fused update — "
    "gluon.TrainStep) or phased (the legacy record/backward/Trainer.step "
    "three-phase sequence)", ["path"])
step_donated_bytes = counter(
    "step_donated_bytes",
    "Bytes of parameter + optimizer-state buffers donated into "
    "whole-step dispatches so the weights update in place (HBM reuse "
    "instead of a second copy of the model)")

step_scalar_operands = gauge(
    "step_scalar_operands",
    "Host-resident leaves (Python scalars, NumPy arrays) among the "
    "operands of the built whole-step program: each is a separate "
    "host-to-device transfer inside every call. 4 when sound — lr / wd / "
    "update counts / hyper-parameters travel as one host array per "
    "family; 3 more per trained parameter means Python scalars leaked "
    "back into the call (gluon/train_step.py)")

# -- graph-pass pipeline (mxnet_tpu/passes/; docs/passes.md) ----------------
pass_applied_total = counter(
    "pass_applied_total",
    "Graph-pass executions by pass name — one per pass per pipeline "
    "build (a new block variant / input signature), never per step",
    ["pass"])
pass_rewrite_ms = histogram(
    "pass_rewrite_ms",
    "Wall ms one graph pass spent rewriting one captured jaxpr "
    "(trace-time cost, amortized over every later dispatch)",
    ["pass"], buckets=_PASS_MS_BUCKETS)

# -- input pipeline (gluon/data/dataloader.py device_prefetch) --------------
data_prefetch_total = counter(
    "data_prefetch_total",
    "Batches pushed through the DataLoader device-prefetch stage "
    "(async jax.device_put issued ahead of the consuming step)")
data_prefetch_depth = gauge(
    "data_prefetch_depth",
    "Batches currently resident in the DataLoader device-prefetch "
    "buffer (transferred or in flight, not yet consumed)")


# -- checkpointing (checkpoint/manager.py; docs/checkpointing.md) -----------
ckpt_save_total = counter(
    "ckpt_save_total",
    "Checkpoint saves by mode (replicated / sharded) and outcome "
    "(ok / error)", ["mode", "outcome"])
ckpt_save_ms = histogram(
    "ckpt_save_ms",
    "Checkpoint save wall time in ms: snapshot capture through commit "
    "rename (async saves: measured on the IO thread at commit, so this "
    "is total latency, NOT time the training loop was blocked)",
    buckets=_CKPT_MS_BUCKETS)
ckpt_bytes_total = counter(
    "ckpt_bytes_total",
    "Bytes of training state committed to checkpoints (this rank's "
    "share in sharded mode)")
ckpt_restore_total = counter(
    "ckpt_restore_total",
    "Checkpoint restore attempts by outcome (ok / corrupt / not_found / "
    "error)", ["outcome"])


# -- serving (serving/engine.py; docs/serving.md) ---------------------------
serve_request_total = counter(
    "serve_request_total",
    "Serving requests by final outcome (ok / shed / timeout / error)",
    ["model", "outcome"])
serve_request_latency_seconds = histogram(
    "serve_request_latency_seconds",
    "End-to-end request latency: submit -> result ready (queue wait + "
    "batch assembly + compiled forward); p50/p99 derive from the buckets",
    ["model"], buckets=_SERVE_LATENCY_BUCKETS)
serve_queue_depth = gauge(
    "serve_queue_depth",
    "Requests waiting in the admission queue right now", ["model"])
serve_in_flight = gauge(
    "serve_in_flight",
    "Requests inside the batch currently executing", ["model"])
serve_batch_total = counter(
    "serve_batch_total", "Micro-batches executed", ["model"])
serve_batch_size = histogram(
    "serve_batch_size",
    "Real rows per executed micro-batch, BEFORE padding to the bucket "
    "(bucket fill)", ["model"], buckets=_SERVE_BATCH_BUCKETS)
serve_padded_rows_total = counter(
    "serve_padded_rows_total",
    "Padding rows added to round batches up to their compile bucket",
    ["model"])
serve_shed_total = counter(
    "serve_shed_total",
    "Requests rejected at admission — queue bound exceeded -> Overloaded",
    ["model"])
serve_timeout_total = counter(
    "serve_timeout_total",
    "Requests that hit their deadline before a result was ready",
    ["model"])
serve_dispatch_total = counter(
    "serve_dispatch_total",
    "Micro-batches dispatched to the device (the pipelined engine "
    "dispatches ahead of completion, so this leads serve_batch_total "
    "by the in-flight window)", ["model"])
serve_inflight_batches = gauge(
    "serve_inflight_batches",
    "Dispatched-but-unsettled micro-batches right now (pipeline window "
    "fill; >1 means host assembly is overlapping device compute)",
    ["model"])
serve_class_queue_depth = gauge(
    "serve_class_queue_depth",
    "Requests queued per priority class (serving/scheduler.py "
    "strict-priority dequeue)", ["model", "cls"])
serve_class_shed_total = counter(
    "serve_class_shed_total",
    "Requests shed at admission per priority class, by reason: 'queue' "
    "(shared bound hit -> Overloaded) or 'rate' (class token bucket "
    "empty -> RateLimited)", ["model", "cls", "reason"])
serve_drain_dropped_total = counter(
    "serve_drain_dropped_total",
    "Requests force-dropped unserved because stop(drain=True) hit its "
    "bounded drain deadline (or the engine was never started)",
    ["model"])
serve_trace_total = counter(
    "serve_trace_total",
    "Sampled request traces frozen into the reqtrace ring, by terminal "
    "outcome (ok / shed / timeout / error); at MXTPU_TRACE_SAMPLE=0 "
    "this never moves (observability/reqtrace.py)",
    ["model", "outcome"])
serve_slo_burn_rate = gauge(
    "serve_slo_burn_rate",
    "Per-class SLO burn rate over the rolling MXTPU_SLO_WINDOW_S "
    "window: windowed bad fraction / error budget (1 - "
    "MXTPU_SLO_TARGET). 1.0 = burning budget exactly as fast as "
    "allowed; above MXTPU_SLO_BURN_MAX the replica drops from /readyz "
    "rotation", ["model", "cls"])
serve_slo_violation_total = counter(
    "serve_slo_violation_total",
    "Requests that violated their class SLO, by kind: 'latency' "
    "(served but over the objective), 'shed', 'timeout', or 'error'",
    ["model", "cls", "kind"])


# -- autoregressive decode (decode/engine.py; docs/decode.md) ---------------
decode_tokens_total = counter(
    "decode_tokens_total",
    "Tokens generated by the decode engine (one per host-side sample "
    "off a settled prefill or decode step)", ["model"])
decode_sequence_total = counter(
    "decode_sequence_total",
    "Decode sequences retired, by reason: 'eos', 'max_tokens', "
    "'context_full' (KV slot row exhausted), 'abandoned' (client "
    "claimed timeout mid-generation), 'stopped', or 'error'",
    ["model", "reason"])
decode_slot_occupancy = gauge(
    "decode_slot_occupancy",
    "KV-cache slots owned by live sequences right now, out of the "
    "engine's fixed MXTPU_DECODE_SLOTS pool", ["model"])
decode_prefill_ms = histogram(
    "decode_prefill_ms",
    "Prompt prefill wall time per joined sequence: dispatch of the "
    "bucket-padded prompt through logits settled (the device half of "
    "time-to-first-token)", ["model"], buckets=_DECODE_MS_BUCKETS)
decode_step_ms = histogram(
    "decode_step_ms",
    "One fixed-shape (num_slots, 1) decode step: dispatch through "
    "logits settled — the inter-token latency floor every active "
    "sequence shares", ["model"], buckets=_DECODE_MS_BUCKETS)
decode_ttft_ms = histogram(
    "decode_ttft_ms",
    "Time-to-first-token per sequence: submit -> first sampled token "
    "(queue wait + slot wait + prefill); the latency the decode SLO "
    "plane judges interactive classes on", ["model"],
    buckets=_DECODE_MS_BUCKETS)


# -- observability plane (mxnet_tpu/observability/; docs/observability.md) --
numerics_trip_total = counter(
    "numerics_trip_total",
    "MXTPU_NUMERICS is-finite checks that tripped, by instrumented "
    "program label (observability.numerics)", ["label"])
flight_events_total = counter(
    "flight_events_total",
    "Flight-recorder events appended, by kind (observability.flight; "
    "the ring is bounded — this counter is the lifetime total)", ["kind"])
postmortem_dump_total = counter(
    "postmortem_dump_total",
    "Postmortem bundles written, by reason prefix (watchdog / preempt / "
    "numerics / crash / exit / periodic / manual)", ["reason"])


# -- sharding (mxnet_tpu/sharding; docs/sharding.md) ------------------------
sharding_plan_applied_total = counter(
    "sharding_plan_applied_total",
    "ShardingPlan.apply placements: every param (+grad) laid out on the "
    "plan's mesh via NamedSharding — once per trainer, re-counted after "
    "a checkpoint restore re-places arrays", ["label"])
sharding_mesh_axis_size = gauge(
    "sharding_mesh_axis_size",
    "Resolved size of each mesh axis of the most recently applied plan "
    "(-1 specs shown post-inference, so dp=-1 on 8 devices reads 8)",
    ["axis"])
sharding_pass_stamp_total = counter(
    "sharding_pass_stamp_total",
    "ShardingPass stamps: one per pipeline build whose context carried "
    "a plan (per seam kind) — accumulated at trace time, "
    "never per step", ["label", "kind"])


def record_sharding_apply(label, axis_sizes, params=0):
    """One plan application: `axis_sizes` is the resolved {axis: size}
    mesh shape, `params` the number of parameters placed.  Mirrored to
    the flight recorder so postmortems show which plan a run trained
    under."""
    _flight_record("sharding_apply", label=str(label),
                   mesh=dict(axis_sizes), params=int(params))
    if not REGISTRY.enabled:
        return
    sharding_plan_applied_total.labels(label).inc()
    for axis, size in axis_sizes.items():
        sharding_mesh_axis_size.labels(str(axis)).set(int(size))


def record_sharding_stamp(label, kind):
    """One ShardingPass stamp on a pipeline build."""
    if not REGISTRY.enabled:
        return
    sharding_pass_stamp_total.labels(label, kind).inc()


# -- elastic training (mxnet_tpu/elastic; docs/elasticity.md) ---------------
elastic_restart_total = counter(
    "elastic_restart_total",
    "Elastic topology-change events by origin: 'supervisor' — "
    "tools/supervisor.py relaunched the job after a rank death; "
    "'reenter' — a live trainer swapped plans in-process via "
    "elastic.reenter()", ["reason"])
reshard_ms = histogram(
    "reshard_ms",
    "Wall ms of one plan-crossing state move, by site: 'restore' — "
    "CheckpointManager re-placing a checkpoint's host-gathered arrays "
    "under a different plan; 'offline' — elastic.reshard_checkpoint "
    "rewriting a checkpoint dir for a target mesh; 'reenter' — the "
    "in-process plan swap (re-place + TrainStep rebuild)", ["site"],
    buckets=_CKPT_MS_BUCKETS)
world_generation = gauge(
    "world_generation",
    "Which incarnation of the elastic job this process runs: 0 at "
    "first launch, +1 per supervisor restart / in-process reenter() "
    "(mirrors the flight identity's generation field)")


def record_elastic_restart(reason, generation=None):
    """One topology-change event; also pins the world_generation gauge
    when the new generation is known. Mirrored to the flight recorder
    so postmortems show every incarnation boundary."""
    _flight_record("elastic_restart", reason=str(reason),
                   generation=generation)
    if not REGISTRY.enabled:
        return
    elastic_restart_total.labels(str(reason)).inc()
    if generation is not None:
        world_generation.set(int(generation))


def record_reshard(ms, saved_world=None, target_world=None,
                   site="restore"):
    """One plan-crossing state move of `ms` wall milliseconds."""
    _flight_record("reshard", ms=ms, site=str(site),
                   saved_world=saved_world, target_world=target_world)
    if not REGISTRY.enabled:
        return
    reshard_ms.labels(str(site)).observe(float(ms))


def set_world_generation(g):
    """Pin the world_generation gauge (elastic.bump_generation)."""
    if not REGISTRY.enabled:
        return
    world_generation.set(int(g))


def record_numerics_trip(label):
    """One tripped numerics check for the program `label`."""
    if not REGISTRY.enabled:
        return
    numerics_trip_total.labels(label).inc()


def record_flight_event(kind):
    """One event appended to the flight-recorder ring."""
    if not REGISTRY.enabled:
        return
    flight_events_total.labels(kind).inc()


def record_postmortem(reason):
    """One postmortem bundle written for `reason`."""
    if not REGISTRY.enabled:
        return
    postmortem_dump_total.labels(reason).inc()


def _flight_record(kind, **fields):
    """Mirror a telemetry touchpoint into the flight recorder (lazy and
    guarded — a broken observability layer must not break metrics)."""
    try:
        from ..observability import flight as _flight

        _flight.record(kind, **fields)
    except Exception:
        pass


# -- helpers ----------------------------------------------------------------

def nbytes_of(x):
    """Byte size of an array-ish (jax.Array / numpy / NDArray _data)."""
    nb = getattr(x, "nbytes", None)
    if nb is not None:
        return int(nb)
    size = getattr(x, "size", None)
    itemsize = getattr(getattr(x, "dtype", None), "itemsize", None)
    if size is not None and itemsize is not None:
        return int(size) * int(itemsize)
    return 0


_XLA_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_xla_tls = threading.local()
# JAX fires the trace event around a CACHED function: a hit takes
# microseconds and traced nothing, and the jnp calls inside one whole-step
# trace make thousands of them (4,146 events in a toy run, 4,044 under a
# millisecond), which would roll the ring.  The counter adds up all of
# them; the ring gets the traces that took at least this long.
_TRACE_RECORD_FLOOR_S = 1e-3


def _fun_of(fun_name):
    """JAX names a program ``whole_step`` when it traces it and
    ``jit(whole_step)`` when it lowers or compiles it: one name for both."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        return name[name.index("(") + 1:-1]
    return name


def _on_xla_duration(event, duration, fun_name=None, **_kw):
    stage = _XLA_STAGE_OF_EVENT.get(event)
    if stage is None:
        return
    # the event fires at the END of what it timed: back-date the record
    # onto the clock of every other span
    t0 = time.perf_counter() - duration
    counting = REGISTRY.enabled
    if counting:
        xla_compile_seconds_total.labels(stage).inc(duration)
    # JAX (0.9) reports a cache hit's retrieval time from INSIDE the
    # region it times as backend_compile_duration, on the same thread and
    # without the program's name: the backend event that follows a load is
    # that load, not a build, and gives the load its name
    if stage == "cache_load":
        _xla_tls.load = (t0, duration)
        return
    if stage != "backend":
        if stage != "trace" or duration >= _TRACE_RECORD_FLOOR_S:
            _spans.record("xla." + stage, "compile", t0, duration,
                          fun=_fun_of(fun_name))
        return
    load = getattr(_xla_tls, "load", None)
    _xla_tls.load = None
    how = "built" if load is None else "loaded"
    fun = _fun_of(fun_name)
    if load is not None:
        _spans.record("xla.cache_load", "compile", *load, fun=fun)
    _spans.record("xla.backend", "compile", t0, duration, fun=fun, how=how)
    if counting:
        xla_programs_total.labels(how).inc()
        # the black box keeps WHICH program arrived (JAX's `fun_name`,
        # `jit(whole_step)`) and WHEN (perf_counter `pc`, the spans'
        # clock): a reader can tell set-up's compiles from those of a
        # later phase of the process, and name the ones built anew
        _flight_record("xla_compile", name=fun_name, how=how,
                       seconds=duration,
                       load_seconds=load[1] if load else 0.0)


def install_compile_listener():
    """Register the one jax.monitoring listener behind
    xla_compile_seconds_total / xla_programs_total and the span ring's
    xla.<stage> records (idempotent; called at import of mxnet_tpu,
    before anything compiles)."""
    if getattr(_on_xla_duration, "installed", False):
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_xla_duration)
    _on_xla_duration.installed = True


def record_compile(block, variant, seconds):
    _flight_record("compile", block=str(block), variant=str(variant),
                   seconds=seconds)
    if not REGISTRY.enabled:
        return
    jit_compile_total.labels(block, variant).inc()
    jit_compile_seconds.labels(block, variant).observe(seconds)


def record_trace(block, variant):
    if not REGISTRY.enabled:
        return
    jit_trace_total.labels(block, variant).inc()


def record_serve_request(model, outcome, seconds=None):
    """One finished serving request. `outcome` is ok / shed / timeout /
    error; `seconds` (when the request made it far enough to have a
    latency) lands in the latency histogram. Shed and timeout also bump
    their dedicated counters so overload is visible at a glance."""
    if outcome != "ok":  # ok requests are too hot for the ring; failures
        _flight_record("serve_" + str(outcome), model=str(model))
    if not REGISTRY.enabled:
        return
    serve_request_total.labels(model, outcome).inc()
    if outcome == "shed":
        serve_shed_total.labels(model).inc()
    elif outcome == "timeout":
        serve_timeout_total.labels(model).inc()
    if seconds is not None:
        serve_request_latency_seconds.labels(model).observe(seconds)


def record_serve_trace(model, outcome):
    """One sampled request trace frozen into the reqtrace ring."""
    if not REGISTRY.enabled:
        return
    serve_trace_total.labels(model, outcome).inc()


def set_slo_burn(model, cls, burn):
    """Publish a class's fresh SLO burn rate (reqtrace.slo_observe and
    every slo_status read keep this live)."""
    if not REGISTRY.enabled:
        return
    serve_slo_burn_rate.labels(model, cls).set(float(burn))


def record_slo_violation(model, cls, kind):
    """One request that blew its class objective, by violation kind."""
    if not REGISTRY.enabled:
        return
    serve_slo_violation_total.labels(model, cls, kind).inc()


def record_serve_batch(model, rows, bucket):
    """One executed micro-batch: `rows` real rows padded up to `bucket`."""
    _flight_record("serve_batch", model=str(model), rows=int(rows),
                   bucket=int(bucket))
    if not REGISTRY.enabled:
        return
    serve_batch_total.labels(model).inc()
    serve_batch_size.labels(model).observe(rows)
    if bucket > rows:
        serve_padded_rows_total.labels(model).inc(bucket - rows)


def record_decode_prefill(model, ms, bucket, slot):
    """One sequence joined a KV slot: prompt prefilled through a seq-len
    bucket rung. Lands in the flight ring as ``decode_join`` (joins are
    rare enough to ring; per-token events are not)."""
    _flight_record("decode_join", model=str(model), bucket=int(bucket),
                   slot=int(slot), ms=round(float(ms), 3))
    if not REGISTRY.enabled:
        return
    decode_prefill_ms.labels(model).observe(ms)


def record_decode_step(model, ms, active):
    """One settled (num_slots, 1) decode step with `active` live slots.
    Too hot for the flight ring — histogram only."""
    if not REGISTRY.enabled:
        return
    decode_step_ms.labels(model).observe(ms)


def record_decode_tokens(model, n=1):
    if not REGISTRY.enabled:
        return
    decode_tokens_total.labels(model).inc(n)


def record_decode_retire(model, reason, tokens, ttft_s=None):
    """One sequence retired (slot freed), by reason; `ttft_s` feeds the
    time-to-first-token histogram when the sequence got that far."""
    _flight_record("decode_retire", model=str(model), reason=str(reason),
                   tokens=int(tokens))
    if not REGISTRY.enabled:
        return
    decode_sequence_total.labels(model, reason).inc()
    if ttft_s is not None:
        decode_ttft_ms.labels(model).observe(ttft_s * 1e3)


def set_decode_occupancy(model, n):
    if not REGISTRY.enabled:
        return
    decode_slot_occupancy.labels(model).set(int(n))


def record_ckpt_save(mode, ms, nbytes, outcome="ok"):
    """One finished checkpoint save: `ms` capture->commit wall ms,
    `nbytes` of committed array payload (this rank's share)."""
    _flight_record("ckpt_save", mode=str(mode), ms=ms, bytes=int(nbytes),
                   outcome=str(outcome))
    if not REGISTRY.enabled:
        return
    ckpt_save_total.labels(mode, outcome).inc()
    if outcome == "ok":
        ckpt_save_ms.observe(ms)
        ckpt_bytes_total.inc(nbytes)


def record_ckpt_restore(outcome):
    """One restore attempt: ok / corrupt / not_found / error."""
    _flight_record("ckpt_restore", outcome=str(outcome))
    if not REGISTRY.enabled:
        return
    ckpt_restore_total.labels(outcome).inc()


def record_fallback(block):
    if not REGISTRY.enabled:
        return
    hybridize_fallback_total.labels(block).inc()


def record_attention_fallback(reason):
    if not REGISTRY.enabled:
        return
    attention_kernel_fallback_total.labels(reason).inc()


def set_attention_maskfree_share(by_kernel):
    if not REGISTRY.enabled:
        return
    for kernel, share in by_kernel.items():
        attention_maskfree_share.labels(kernel).set(share)


_attention_plans = [0, 0]    # signatures planned: fused backward, in all


def record_attention_backward_plan(fused):
    if not REGISTRY.enabled:
        return
    _attention_plans[0] += bool(fused)
    _attention_plans[1] += 1
    attention_fused_backward_share.set(
        _attention_plans[0] / _attention_plans[1])


def set_attention_pairs(mask, visited, kept):
    if not REGISTRY.enabled:
        return
    attention_pairs_visited.labels(mask).set(visited)
    attention_pairs_kept.labels(mask).set(kept)


_qk_prep_sites = [0, 0]      # traced call sites: on the kernels, in all


def _record_kernel_site(sites, share, kernels):
    """One more traced call site of an op that chooses between its fused
    kernels and a composition of XLA ops: the tally and its gauge."""
    if not REGISTRY.enabled:
        return
    sites[0] += bool(kernels)
    sites[1] += 1
    share.set(sites[0] / sites[1])


def record_qk_prep_site(kernels):
    _record_kernel_site(_qk_prep_sites, qk_prep_kernel_share, kernels)


_mla_heads_sites = [0, 0]    # traced call sites: on the kernels, in all


def record_mla_heads_site(kernels):
    _record_kernel_site(_mla_heads_sites, mla_heads_kernel_share, kernels)


_kda_scan_calls = {"kernel": 0, "composition": 0}


def record_kda_scan(kernels, chunks, kept_bytes):
    """One more traced call site of `kda_scan`: the path it took, its
    chunks and what its backward keeps."""
    if not REGISTRY.enabled:
        return
    path = "kernel" if kernels else "composition"
    _kda_scan_calls[path] += 1
    kda_scan_calls.labels(path).set(_kda_scan_calls[path])
    kda_scan_chunks.set(chunks)
    kda_scan_kept_bytes.set(kept_bytes)


def set_looped_stack(steps):
    """A program was traced whose layer stack runs ``steps`` times as one
    rolled loop: one copy of the stack."""
    if not REGISTRY.enabled:
        return
    looped_stack_copies.set(1)
    ut_steps.set(steps)


_short_conv_sites = [0]     # call sites of the mix traced so far


def record_short_conv_site():
    _short_conv_sites[0] += 1


def short_conv_sites_traced():
    """Call sites of `gated_short_conv` traced so far in the process: a
    model reads it before and after it traces its stack."""
    return _short_conv_sites[0]


def set_decoder_stack(kinds, sites_before):
    """A stack of layers of several kinds was traced: ``kinds`` is
    {(operator, feed_forward): layers}, ``sites_before`` what
    `short_conv_sites_traced` read before the stack."""
    if not REGISTRY.enabled:
        return
    decoder_layers.clear()
    for (operator, feed_forward), n in kinds.items():
        decoder_layers.labels(operator, feed_forward).set(n)
    short_conv_sites.set(_short_conv_sites[0] - sites_before)


# the device array the last step's exit objective produced, (steps,); it
# stays on the device until somebody asks
_staged_exit_mass = []


def stage_exit_mass(mass):
    """A step's exit objective produced ``mass``, the mean exit
    probability of each loop step, on the device.  Keeps the array,
    fetches nothing, like `stage_moe_load`."""
    if REGISTRY.enabled:
        _staged_exit_mass[:] = [mass]


def flush_exit_mass():
    """Fetch what the last step staged and set ``exit_mass{step}``;
    returns the list of the loop steps' masses, or None where no step
    staged any.  One device-to-host read: call it where the loop reads
    the loss."""
    if not _staged_exit_mass:
        return None
    import jax

    mass = [float(m) for m in jax.device_get(_staged_exit_mass[0])]
    for t, m in enumerate(mass):
        exit_mass.labels(str(t + 1)).set(m)
    return mass


def record_transfer(direction, nbytes):
    if not REGISTRY.enabled:
        return
    transfer_total.labels(direction).inc()
    transfer_bytes_total.labels(direction).inc(nbytes)


def record_sync(site, seconds):
    if not REGISTRY.enabled:
        return
    sync_total.labels(site).inc()
    sync_blocked_seconds_total.labels(site).inc(seconds)


def record_collective(op, nbytes, seconds):
    _flight_record("collective", op=str(op), bytes=int(nbytes))
    if not REGISTRY.enabled:
        return
    collective_total.labels(op).inc()
    collective_bytes_total.labels(op).inc(nbytes)
    collective_seconds_total.labels(op).inc(seconds)


def set_flop_budget(flops, peak=None):
    """Declare the per-step FLOP budget (and optionally the accelerator
    peak) so observe_step can keep the MFU gauge live. `flops` is the
    cost of ONE optimizer step (fwd+bwd+update), e.g. from XLA's
    cost_analysis.  Without `peak` the
    denominator is this device's DEVICE_PEAKS entry; on a device the
    table does not list the peak stays unset and the MFU gauge silent."""
    flops_per_step.set(flops)
    if peak is None:
        try:
            peak = device_peaks()["bf16_flops"]
        except KeyError:
            peak = 0.0
    peak_flops.set(peak)


def record_update_dispatch(path, donated_bytes=0):
    """One optimizer-update jit dispatch on `path` (fused / fused_norm /
    per_param / sparse); `donated_bytes` counts the weight/state buffers
    handed to XLA for in-place reuse."""
    if not REGISTRY.enabled:
        return
    update_dispatch_total.labels(path).inc()
    if donated_bytes:
        update_donated_bytes.inc(donated_bytes)


def record_step_dispatch(path, donated_bytes=0):
    """One executed training step on `path` (whole_step / phased);
    `donated_bytes` counts the param+state buffers handed to XLA for
    in-place reuse by the whole-step dispatch."""
    if not REGISTRY.enabled:
        return
    step_dispatch_total.labels(path).inc()
    if donated_bytes:
        step_donated_bytes.inc(donated_bytes)


moe_rows_routed_here = gauge(
    "moe_rows_routed_here",
    "Token-to-expert assignments of the last step that an expert layer "
    "routed to the experts it holds (of tokens x experts-per-token in "
    "all): the rows its grouped products worked on. Produced on the "
    "device; set by flush_moe_load()", ["layer"])
moe_expert_load_max_over_mean = gauge(
    "moe_expert_load_max_over_mean",
    "Rows of the busiest held expert over the mean of the held experts' "
    "rows in the last step: 1 is an even load. Produced on the device; "
    "set by flush_moe_load()", ["layer"])
moe_buffer_rows = gauge(
    "moe_buffer_rows",
    "Rows of the buffer an expert layer's last step worked on: the first "
    "of the layer's static lengths (parallel.moe.buffer_rungs) that holds "
    "moe_rows_routed_here. The layer takes it on the device; "
    "flush_moe_load() works it out again from the fetched count", ["layer"])

moe_bias_moved_share = gauge(
    "moe_bias_moved_share",
    "Of an expert layer whose router selects on score + bias: the share "
    "of the last step's token-to-expert assignments that the largest "
    "plain scores would not have made. 0 says the bias did nothing. "
    "Produced on the device; set by flush_moe_load()", ["layer"])

# layer -> (the device array its last step produced, (2,) or with a
# selection bias (3,), the layer's buffer lengths); the array stays on the
# device until somebody asks
_staged_moe_load = {}


def stage_moe_load(layer, load, rungs):
    """An expert layer's step produced ``load`` = [rows routed here,
    load max over mean] and, under a selection bias, [the share of the
    assignments it moved] on the device, on a buffer of one of ``rungs``
    rows.  Keeps the array, fetches nothing: a step gains no host sync."""
    if REGISTRY.enabled:
        _staged_moe_load[layer] = (load, rungs)


def flush_moe_load():
    """Fetch what the last step staged and set the gauges; returns
    {layer: (rows routed here, load max over mean)}.  This is the one
    device-to-host read, so call it where the loop reads the loss."""
    import jax

    from ..parallel.moe import rung_index

    out = {}
    layers = sorted(_staged_moe_load)
    loads = jax.device_get([_staged_moe_load[n][0] for n in layers])
    for layer, (rows, ratio, *moved) in zip(layers, loads):
        rows, ratio = float(rows), float(ratio)
        rungs = _staged_moe_load[layer][1]
        moe_rows_routed_here.labels(layer).set(rows)
        moe_expert_load_max_over_mean.labels(layer).set(ratio)
        moe_buffer_rows.labels(layer).set(rungs[rung_index(rungs, rows)])
        if moved:
            moe_bias_moved_share.labels(layer).set(float(moved[0]))
        out[layer] = (rows, ratio)
    return out


def record_step_scalar_operands(operands):
    """The whole-step program was built for `operands`: count the leaves
    that live on the host (Python scalars, NumPy arrays and scalars)."""
    if not REGISTRY.enabled:
        return
    import jax
    import numpy as np

    step_scalar_operands.set(sum(
        isinstance(x, (bool, int, float, np.ndarray, np.generic))
        for x in jax.tree_util.tree_leaves(operands)))


def record_pass(name, ms):
    """One graph pass rewrote one captured jaxpr in `ms` wall ms."""
    if not REGISTRY.enabled:
        return
    pass_applied_total.labels(name).inc()
    pass_rewrite_ms.labels(name).observe(ms)


def record_device_prefetch(depth):
    """One batch entered the DataLoader device-prefetch buffer, which now
    holds `depth` batches ahead of the consumer."""
    if not REGISTRY.enabled:
        return
    data_prefetch_total.inc()
    data_prefetch_depth.set(depth)


def record_fused_bucket(site, params):
    """One fused bucket dispatched at `site` holding `params` parameters."""
    if not REGISTRY.enabled:
        return
    fused_bucket_size.labels(site).observe(params)


def observe_step(seconds=None, examples=None):
    """Record one training step. `seconds` is the interval since the
    previous step's completion (None on the first step — counted, not
    timed); `examples` is the global batch size."""
    if not REGISTRY.enabled:
        return
    step_total.inc()
    if seconds is None or seconds <= 0:
        return
    step_time_seconds.observe(seconds)
    if examples:
        examples_per_second.set(examples / seconds)
    budget = flops_per_step.value
    peak = peak_flops.value
    if budget > 0 and peak > 0:
        mfu_ratio.set(budget / seconds / peak)
