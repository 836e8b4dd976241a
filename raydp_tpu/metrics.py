"""Typed telemetry registries + the failure flight recorder.

The per-subsystem report dicts (``op_counts()``, ``shuffle_stage_report()``,
``serving_report()``, estimator epoch timers) grew by accretion, one PR at a
time, each with its own naming and its own collection path. This module is
the designed replacement — the ``knobs.py`` pattern applied to telemetry:

- **Metrics registry** — every counter/gauge/histogram is declared here
  (name, kind, unit, owning subsystem, one-line doc). Process-local
  increments are a dict update under one lock; per-process state is
  harvested over the existing actor RPC plane through the
  ``__rdt_metrics__`` intrinsic (beside ``__rdt_spans__``), and
  :func:`metrics_report` merges driver, executors, and node agents into one
  view that subsumes the legacy report dicts (which remain as compatible
  views over the same counters).
- **Span registry** — every literal ``profiler.trace(...)`` span name is
  declared here too; dynamic families (``task:<Step>``) are declared as
  prefixes. The ``telemetry-registry`` rdtlint rule statically checks
  literal span/metric/event names against these registries, and the tables
  in ``doc/observability.md`` are GENERATED from them
  (``python -m raydp_tpu.metrics --write-docs``).
- **Flight recorder** — a bounded per-process ring of structured events
  (faults fired, object losses, recovery rounds, re-seals, executor
  down/up, hedges, aborts). When an action surfaces a ``StageError`` /
  ``ServingError`` the driver harvests every process's ring into a
  ``blackbox-<action>.json`` postmortem bundle (:func:`write_blackbox`), so
  chaos runs leave artifacts instead of log archaeology.

This module must stay **stdlib-only at import** (the same contract as
``knobs.py``): it is loaded standalone by the linter and imported by
bootstrap-adjacent paths. Anything that needs the runtime (report merging,
blackbox harvest) imports it lazily inside the function.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

#: the two span classes (``Span.kind``). A PHASE span happens a handful of
#: times an action or a fit: ``profiler.trace``/``open_span`` record it in the
#: process's span ring, always. A STEP span happens once or more a batch:
#: ``profiler.step`` records it only while a ``jax.profiler`` session captures
#: a device trace, and then into that trace - it never enters the ring.
PHASE = "phase"
STEP = "step"
#: a third class names no host span at all: a SCOPE is a ``jax.named_scope``
#: (or a flax module's name) inside a model, which the compiler carries into
#: every device op's ``op_name``; a device trace's ops are attributed to it
#: (``chipbench/trace/scopes.py``). Declared here so that the table of spans
#: is the whole vocabulary a trace is read with.
SCOPE = "scope"

#: histograms are summary-shaped (count/sum/min/max), not bucketed: every
#: producer is a wall-clock or size observation whose tails the driver can
#: read off max, and bucket layouts would be one more thing to keep in sync
_HIST_ZERO = {"count": 0, "sum": 0.0, "min": None, "max": None}


@dataclass(frozen=True)
class Metric:
    """One declared metric."""

    name: str
    kind: str          # COUNTER | GAUGE | HISTOGRAM
    unit: str          # "1", "s", "rows", "bytes" — doc only
    subsystem: str     # "scheduler" | "store" | "serving" | ...
    doc: str
    #: the single optional label dimension ("" = unlabeled)
    label: str = ""


@dataclass(frozen=True)
class Span:
    """One declared trace-span name (or a dynamic family prefix)."""

    name: str
    subsystem: str
    doc: str
    #: True = ``name`` is a prefix of runtime-formatted span names
    #: (f-strings); the linter only checks literal names, these rows exist
    #: so the doc table is the complete span vocabulary
    dynamic: bool = False
    #: PHASE (ring, through ``profiler.trace``/``open_span``), STEP (device
    #: trace only, through ``profiler.step``) or SCOPE (device ops' op_name)
    kind: str = PHASE


@dataclass(frozen=True)
class Event:
    """One declared flight-recorder event kind."""

    kind: str
    subsystem: str
    doc: str


def _m(name, kind, unit, subsystem, doc, label=""):
    return Metric(name=name, kind=kind, unit=unit, subsystem=subsystem,
                  doc=doc, label=label)


#: declaration order is presentation order in the generated tables
_ALL_METRICS = [
    # ---- scheduler / engine -------------------------------------------------
    _m("sched_tasks_dispatched_total", COUNTER, "1", "scheduler",
       "Task attempts submitted to executors (retries and speculative "
       "backups included).", label="executor"),
    _m("sched_speculated_total", COUNTER, "1", "scheduler",
       "Tasks that received a speculative backup."),
    _m("sched_speculation_won_total", COUNTER, "1", "scheduler",
       "Tasks whose speculative backup finished first."),
    _m("sched_executor_down_total", COUNTER, "1", "scheduler",
       "Times an executor was marked unreachable by task placement.",
       label="executor"),
    _m("sched_executor_up_total", COUNTER, "1", "scheduler",
       "Times a down-marked executor answered again and re-entered task "
       "placement (the executor_down symmetry).", label="executor"),
    _m("pool_size", GAUGE, "1", "scheduler",
       "Live executors in the elastic pool (draining members excluded)."),
    _m("pool_drains_total", COUNTER, "1", "scheduler",
       "Graceful executor drains started (retire_executor / autoscale "
       "scale-down)."),
    _m("pool_scaled_up_total", COUNTER, "1", "scheduler",
       "Executors the autoscale controller added to the pool."),
    _m("pool_scaled_down_total", COUNTER, "1", "scheduler",
       "Executors the autoscale controller drained out of the pool."),
    _m("sched_tenant_dispatched_total", COUNTER, "1", "scheduler",
       "Task attempts dispatched per tenant (the fair-share observability "
       "column: under contention the per-tenant rates track the "
       "configured weights).", label="tenant"),
    _m("pool_admission_parked_total", COUNTER, "1", "scheduler",
       "Actions that parked at admission because the pool's queued "
       "backlog exceeded RDT_POOL_MAX_QUEUED.", label="tenant"),
    _m("pool_admission_rejects_total", COUNTER, "1", "scheduler",
       "Actions failed with AdmissionRejected after parking past "
       "RDT_ADMIT_TIMEOUT_S.", label="tenant"),
    _m("pool_backpressure_total", COUNTER, "1", "scheduler",
       "Times dispatch to a host paused on the store high-watermark "
       "(memory backpressure trip transitions, not per-task skips).",
       label="host"),
    _m("sched_locality_hits_total", COUNTER, "1", "scheduler",
       "Task attempts dispatched to their locality-preferred executor "
       "(data-gravity scheduling landed the task where its bytes are)."),
    _m("pool_warm_forks_total", COUNTER, "1", "scheduler",
       "Workers spawned by forking the pre-imported warm-start prototype "
       "instead of cold-spawning a fresh interpreter."),
    _m("pool_warm_refreshes_total", COUNTER, "1", "scheduler",
       "Supervised warm-fork prototype restarts: a latched-failed plane "
       "re-warmed a fresh prototype (bounded by RDT_WARM_FORK_RETRIES) and "
       "returned to fork-fast scale-up."),
    _m("recovery_rounds_total", COUNTER, "1", "recovery",
       "Lineage-recovery rounds that re-executed producers."),
    _m("recovery_blobs_regenerated_total", COUNTER, "1", "recovery",
       "Lost store blobs rebuilt through lineage recovery."),
    _m("stage_aborts_total", COUNTER, "1", "scheduler",
       "Failing stages that ran the abort contract (drain + free)."),
    _m("stream_reseals_total", COUNTER, "1", "shuffle",
       "Pipelined-shuffle seals superseded by a regenerated producer "
       "(generation > 1)."),
    # ---- object store -------------------------------------------------------
    _m("store_ops_total", COUNTER, "1", "store",
       "Store control-plane table operations (a batch call counts one), "
       "per method — the registry view of ObjectStoreServer.op_counts().",
       label="op"),
    _m("store_objects_lost_total", COUNTER, "1", "store",
       "ObjectLostError raised: a blob was gone or unreachable at read."),
    _m("store_fault_in_total", COUNTER, "1", "store",
       "Spilled payloads faulted back into shared memory on read (the "
       "disk-read side of the spill plane)."),
    # ---- tracing / telemetry plane ------------------------------------------
    _m("profiler_spans_dropped_total", COUNTER, "1", "profiler",
       "Trace spans silently evicted from the bounded per-process ring "
       "(RDT_PROFILER_MAX_SPANS) — nonzero means the timeline is "
       "truncated."),
    _m("telemetry_skipped_processes_total", COUNTER, "1", "profiler",
       "Live processes a trace/metrics/blackbox harvest could not reach — "
       "nonzero means the merged view is missing lanes."),
    _m("flightrec_events_dropped_total", COUNTER, "1", "profiler",
       "Flight-recorder events evicted from the bounded ring "
       "(RDT_FLIGHT_MAX_EVENTS)."),
    # ---- fault plane --------------------------------------------------------
    _m("faults_injected_total", COUNTER, "1", "faults",
       "Fault-injection rules fired in this process, per site.",
       label="site"),
    # ---- serving plane ------------------------------------------------------
    _m("serve_requests_total", COUNTER, "1", "serving",
       "predict()/predict_async() requests accepted by the dispatcher."),
    _m("serve_batches_total", COUNTER, "1", "serving",
       "Coalesced micro-batches dispatched to replicas."),
    _m("serve_rows_total", COUNTER, "rows", "serving",
       "Rows dispatched across all micro-batches."),
    _m("serve_hedged_total", COUNTER, "1", "serving",
       "Dispatches duplicated onto a second replica past the hedge "
       "deadline."),
    _m("serve_hedge_won_total", COUNTER, "1", "serving",
       "Hedged dispatches whose second copy responded first."),
    _m("serve_hedge_lost_total", COUNTER, "1", "serving",
       "Duplicate responses discarded after the sibling copy won."),
    _m("serve_rerouted_total", COUNTER, "1", "serving",
       "Dispatches re-routed off a failed/unreachable replica."),
    _m("serve_failed_total", COUNTER, "1", "serving",
       "Requests failed after every replica refused within the re-route "
       "grace (ServingError)."),
    _m("serve_shed_total", COUNTER, "1", "serving",
       "Requests refused at admission with the typed retriable "
       "ServingOverloaded (outstanding queue at RDT_SERVE_MAX_QUEUE)."),
    _m("serve_queue_depth", GAUGE, "1", "serving",
       "Pending + in-flight dispatcher work per serving session, refreshed "
       "on every dispatcher loop pass (an idle session reads 0).",
       label="session"),
    _m("serve_batch_occupancy_rows", HISTOGRAM, "rows", "serving",
       "Rows per dispatched micro-batch (coalescing effectiveness)."),
    _m("serve_request_seconds", HISTOGRAM, "s", "serving",
       "Per-request latency from enqueue to demuxed completion."),
    _m("serve_hot_swaps_total", COUNTER, "1", "serving",
       "Servable hot-swaps completed by a serving session (new version "
       "loaded beside the old, traffic shifted, old retired; guarded-"
       "rollout promotions count here too)."),
    _m("serve_version_requests_total", COUNTER, "1", "serving",
       "Requests answered per live servable version (label "
       "'<session>:v<N>') — the rollout judgment's traffic counter.",
       label="version"),
    _m("serve_version_failed_total", COUNTER, "1", "serving",
       "Requests failed per servable version (the rollout judgment's "
       "error-rate numerator).", label="version"),
    _m("serve_version_request_seconds", HISTOGRAM, "s", "serving",
       "Per-request latency per servable version — the per-version p99 "
       "window a guarded rollout judges the canary on.", label="version"),
    _m("serve_version_weight", GAUGE, "1", "serving",
       "Current dispatch-traffic weight of each live servable version "
       "(0 after a drop/rollback).", label="version"),
    _m("serve_version_replicas", GAUGE, "1", "serving",
       "Replica count of each live servable version (the serving "
       "autoscaler's actuator target).", label="version"),
    _m("serve_unload_failed_total", COUNTER, "1", "serving",
       "Retired replicas that still refused serve_unload at the retry "
       "deadline — their servable's weights stay pinned in that "
       "executor's RAM (loud leak counter; see the unload_failed "
       "event)."),
    _m("serve_rollouts_total", COUNTER, "1", "serving",
       "Guarded rollouts started (RolloutController.run)."),
    _m("serve_rollouts_rolled_back_total", COUNTER, "1", "serving",
       "Guarded rollouts auto-rolled-back on an unhealthy verdict (or "
       "timeout); the complement promoted."),
    _m("serve_scaled_up_total", COUNTER, "1", "serving",
       "Serving-autoscaler replica additions (every live version grows "
       "together)."),
    _m("serve_scaled_down_total", COUNTER, "1", "serving",
       "Serving-autoscaler replica drains after sustained idleness."),
    # ---- continuous pipelines -----------------------------------------------
    _m("stream_epochs_total", COUNTER, "1", "stream",
       "Micro-batch epochs a continuous pipeline completed (transform ran, "
       "result sealed + published to the epoch ledger)."),
    _m("stream_rows_total", COUNTER, "rows", "stream",
       "Input rows ingested across all continuous-pipeline epochs."),
    _m("stream_epoch_seconds", HISTOGRAM, "s", "stream",
       "Wall-clock of one micro-batch epoch (source rows in hand to sealed "
       "+ published result)."),
    _m("stream_windows_total", COUNTER, "1", "stream",
       "Windowed aggregations closed (tumbling/sliding merges over epoch "
       "partials)."),
    _m("stream_replays_total", COUNTER, "1", "stream",
       "Lost epoch blobs re-derived from the source journal "
       "(exactly-once replay rounds; each replayed epoch counts once)."),
    # ---- data feed / training -----------------------------------------------
    _m("feed_phase_seconds", HISTOGRAM, "s", "feed",
       "Feed-pipeline phase walls (decode / h2d), one observation "
       "per timed section — the registry twin of PipelineTimings.",
       label="phase"),
    _m("feed_staged_tables_total", COUNTER, "1", "feed",
       "Multi-column Arrow tables decoded to host arrays, by the path that "
       "decoded them: the native staging kernel or numpy.", label="path"),
    _m("feed_pulls_total", COUNTER, "1", "feed",
       "Batches the train (or eval) loop pulled from the device feed, by "
       "whether one was ready or the feed's queue was empty at the moment of "
       "the pull. A high empty share means the loop waits for the feed; the "
       "chip starves only if the loop is not running ahead of it (a loop "
       "ramping up its run-ahead at an epoch's start also pulls from an "
       "empty queue).",
       label="state"),
    _m("feed_batches_cut_total", COUNTER, "1", "feed",
       "Host batches HostBatchIterator cut, by how: gathered (one gather of "
       "the batch's slice of a part's permutation), joined (the tail of one "
       "part and the head of the next, concatenated) or sliced (a contiguous "
       "view of a decoded block: no shuffle, no copy).", label="cut"),
    _m("train_epoch_seconds", HISTOGRAM, "s", "training",
       "Wall-clock of one training epoch (both estimators)."),
    _m("train_param_bytes_per_process", GAUGE, "bytes", "training",
       "Params + optimizer state resident on this process's devices after "
       "sharded placement (replicated leaves count one copy per device) — "
       "the fsdp-vs-replicated HBM headroom measure."),
    _m("train_padded_rows_total", COUNTER, "rows", "training",
       "Zero rows appended by pad-and-mask feeds to square a ragged final "
       "batch; each padded row is masked out of losses and metrics."),
    _m("train_table_updates_total", COUNTER, "1", "training",
       "Embedding tables a model declared, counted once a built train step "
       "(one a table a fit), by how the step updates them: row-wise (only "
       "the rows a batch looked up are differentiated, updated and written) "
       "or dense (the whole table; the fit's log names why: probe, shape, "
       "accum, pipeline). doc/training.md, the row-wise update.",
       label="path"),
    _m("train_table_walk_total", COUNTER, "1", "training",
       "Row-wise embedding tables, counted once a built train step, by who "
       "walks the rows a batch looked up: shard_local (the table's rows are "
       "split over mesh axes and each shard reads and writes its own slice "
       "of the batch's distinct ids) or global (every chip that holds a "
       "part of the table walks all of them: no mesh, rows not split, or a "
       "step that was not told the state's shardings). doc/training.md, the "
       "row-wise update.",
       label="path"),
    _m("train_table_sum_total", COUNTER, "1", "training",
       "Row-wise embedding tables whose looked-up rows are put together by "
       "a sum over mesh axes (the shard_local ones of "
       "train_table_walk_total), counted once a built train step, by what "
       "that sum carries: real_rows (the batch's distinct ids, rounded up "
       "to a pass of the sum; the fill rows past them stay the zeros they "
       "are on every shard) or all_rows (all B rows of the view: a batch no "
       "larger than one pass). Nothing on one chip or where every shard "
       "walks all of the ids. doc/training.md, the row-wise update.",
       label="carries"),
    _m("train_head_loss_total", COUNTER, "1", "training",
       "Train steps built round a model that brings its own loss "
       "(`loss_rows`), counted once a built step by how the loss is "
       "differentiated: `forward_grad` = the step handed the model the rows' "
       "weights, so the loss takes its gradients inside its forward pass "
       "(`TransformerLM`'s fused head loss: three head products a chunk, none "
       "recomputed in the backward pass). doc/training.md.",
       label="path"),
    _m("moe_slots_total", COUNTER, "1", "training",
       "Expert slots (token, expert choices) the sparse expert layers of a "
       "training model routed, summed on the device inside the train step "
       "and added here with each epoch's loss: `all` is every slot "
       "(experts a token x tokens x expert layers), `max_expert` the slots "
       "of each layer's fullest expert. max_expert / (all / experts) is the "
       "load imbalance a dropless layer pays for; `held` the slots routed "
       "to an expert this chip holds (`experts_held`: one chip's share of an "
       "expert-parallel layer computes those and no other; equal to `all` "
       "where every expert is held); `moved` the slot rows such a share's "
       "walk carried between token order and expert order: the held slots "
       "rounded up to a trip of the walk, layer by layer (`held` <= `moved`; "
       "`moved` = `all` would mean the share moved every slot, held or "
       "not). `held` and `moved` are counted only where a share is held. "
       "doc/training.md.",
       label="kind"),
    _m("moe_router_bias_spread", GAUGE, "1", "training",
       "Sigmoid routing's balancing bias (`routing=\"sigmoid\"`: experts "
       "picked by score + bias, auxiliary-loss-free balancing): max(bias) - "
       "min(bias) of the expert layer where it is widest, as the last train "
       "step of an epoch read it. The bias moves by `bias_update_rate` an "
       "optimizer step towards the experts short of slots, so the spread "
       "says how far the router's own scores are from balanced. "
       "doc/training.md."),
    _m("train_attention_layers_total", COUNTER, "1", "training",
       "Attention layer executions of a training model's step (the layers; "
       "times `total_ut_steps` where the model loops), counted once a built "
       "train step by kind: `window` (a sliding window: a query sees itself and "
       "the window - 1 keys before it), `blockdiff` (the block-diffusion "
       "mask over a clean and a noised copy of each row) or `full` (every "
       "key up to its own); "
       "a latent-attention layer (keys and values from one low-rank latent "
       "a token) counts under its kernel's kind and under `latent` too. "
       "doc/models.md.",
       label="kind"),
    _m("train_attention_forward_total", COUNTER, "1", "training",
       "Attention layer executions of a training model's step (layers x "
       "`total_ut_steps`), counted once a built train "
       "step by how often the step runs their forward attention: `once` "
       "(the block is not recomputed, or `remat_blocks` recomputes it and "
       "keeps the flash kernel's output and row sums, so the recomputation "
       "holds no forward kernel) or `twice` (a recomputed block whose "
       "attention names nothing to keep: `dense`, `ring`). "
       "doc/long_context.md.",
       label="times"),
    _m("train_attention_inputs_total", COUNTER, "1", "training",
       "Attention layer executions of a training model's step (layers x "
       "`total_ut_steps`) in a model whose layers are recomputed "
       "(`remat_blocks`), counted once a built train step by what the "
       "recomputation does with the attention's inputs (q, k and v as the "
       "flash kernel takes them, and the raw projections a head norm or a "
       "gate reads): `kept` (the checkpoint's policy lists them by name: the "
       "backward runs no projection, head norm or RoPE a second time) or "
       "`rebuilt` (it lists none: latent attention, a looped stack, `dense`, "
       "`ring`). Absent where no layer is recomputed. doc/long_context.md.",
       label="inputs"),
    _m("train_sublayer_out_total", COUNTER, "1", "training",
       "Sub-layer outputs that a second norm reads (`sandwich_norms`: the "
       "attention's and the feed-forward's, two a block) in a training "
       "model whose blocks are recomputed (`remat_blocks`), counted once a "
       "built train step (block executions: x `total_ut_steps` where the "
       "model loops) by what the recomputation does with them: `kept` "
       "(the feed-forward's: no forward walk of the held experts and no "
       "down projection runs again) or `rebuilt` (the attention's: its "
       "output projection runs again). Absent where no block is recomputed "
       "or no norm reads them. doc/long_context.md.",
       label="outputs"),
    _m("train_ssm_layers_total", COUNTER, "1", "training",
       "State-space layers (a Mamba-2 mixer alone in its layer) of a "
       "training model, counted once a built train step by what a "
       "recomputed layer does with its scan: `plain` (the layer is not "
       "recomputed) or `rescanned` (`remat_blocks`: the forward scan kernel "
       "runs again in the backward pass and hands the backward kernel the "
       "chunks' states; nothing of the scan is kept). doc/long_context.md.",
       label="scan"),
    _m("ssd_chunks_total", COUNTER, "1", "training",
       "Chunks the state-space scan kernels walk, counted where a kernel's "
       "grid is built (sequences x groups x chunks), by pass: `forward` "
       "(once a built forward kernel, a recomputed layer's second one "
       "included) or `backward`. ops/ssd_scan.py.",
       label="pass"),
    _m("ssm_glue_total", COUNTER, "1", "training",
       "Stages a state-space mixer runs round its scan (the causal "
       "convolution with its SiLU; the gated grouped RMSNorm), counted "
       "once a built layer call a stage by the path the call's shapes "
       "take: `kernel` (one Pallas pass over HBM each way; where the "
       "program is lowered for anything but a TPU the `jax.numpy` form "
       "runs in its place) or `jnp` (a shape the kernels do not take: "
       "`ssm_glue.kernel_ineligible` says why). ops/ssm_glue.py.",
       label="path"),
    _m("train_conv_layers_total", COUNTER, "1", "training",
       "Pairs whose operator is a gated short convolution (`layer_kinds` "
       "`C`: a `ShortConv`, module `short_conv`, where the other pairs have "
       "attention) of a training model, counted once a built train step by "
       "what a recomputed one does with its operator: `plain` (the layer is "
       "not recomputed) or `recomputed` (`remat_blocks`: nothing of the "
       "operator is kept; `W_in u`, the gated convolution and `W_out` run "
       "again in the backward pass). doc/training.md.",
       label="operator"),
    _m("short_conv_total", COUNTER, "1", "training",
       "Gated short convolutions (`C * conv(B * z)` between a convolution "
       "operator's two projections), counted once a built layer call by the "
       "path the call's shapes take: `kernel` (`rdt_gated_conv_fwd|bwd`, one "
       "Pallas pass over HBM each way; where the program is lowered for "
       "anything but a TPU the `jax.numpy` form runs in its place) or `jnp` "
       "(a shape the kernels do not take: `short_conv.kernel_ineligible` "
       "says why). ops/short_conv.py.",
       label="path"),
    _m("train_kda_layers_total", COUNTER, "1", "training",
       "Pairs whose operator is Kimi Delta Attention (`layer_kinds` `K`: a "
       "`KimiDeltaAttention`, module `kda`, where the other pairs have "
       "attention) of a training model, counted once a built train step by "
       "what a recomputed one does with its scan: `plain` (the layer is not "
       "recomputed) or `rescanned` (`remat_blocks`: nothing of the operator "
       "is kept; the projections, the convolution, the gates and the chunked "
       "scan run again in the backward pass). doc/long_context.md.",
       label="scan"),
    _m("kda_scan_total", COUNTER, "1", "training",
       "Scans of a Kimi Delta Attention layer (a delta rule with a decay a "
       "key channel, in chunks), counted once a built layer call by what the "
       "call holds: `kernel` (the shapes are ones `rdt_kda_fwd|bwd` take: in "
       "a program lowered for a TPU the kernels run) and `jnp` (the chunked "
       "`jax.numpy` form with XLA's triangular solve is traced into the "
       "program: alone where `kda_scan.kernel_ineligible` names a reason, and "
       "beside `kernel` as the branch every platform but a TPU runs; only an "
       "interpreted call holds the kernels alone). ops/kda_scan.py.",
       label="path"),
    _m("kda_chunks_total", COUNTER, "1", "training",
       "Chunks a Kimi Delta Attention scan walks, counted where a pass is "
       "built (sequences x heads x chunks), by pass: `forward` (once a built "
       "forward scan, a recomputed layer's second one included) or "
       "`backward` (the chunks formed again and transposed), once a pass "
       "whichever path runs it. ops/kda_scan.py.",
       label="pass"),
    _m("flash_backward_total", COUNTER, "1", "training",
       "Backward passes of the flash-attention kernels, counted where one "
       "is built (a layer call each), by what it is made of: `fused` (one "
       "kernel: the scores and dP formed once a block pair, a K/V head's "
       "dk and dv held in VMEM over its walk) or `split` (two kernels, the "
       "scores and dP formed in each: a sequence whose head's gradients do "
       "not fit, `FUSED_BWD_RESIDENT_BYTES`). ops/flash_attention.py.",
       label="kernels"),
    _m("flash_forward_total", COUNTER, "1", "training",
       "Forward passes of the flash-attention kernels, counted where one "
       "is built (a layer call each), by the unit of a block's "
       "online-softmax update: `chunked` (the q rows of a block no edge "
       "of the mask crosses a chunk at a time, a chunk's QK^T issued "
       "before the softmax and PV of the chunk before it; `_ROW_CHUNK` "
       "rows) or `whole` (a block too small for two chunks is one piece). "
       "ops/flash_attention.py.",
       label="update"),
    _m("flash_blocks_total", COUNTER, "1", "training",
       "(q block, k block) pairs of the flash-attention kernels, counted "
       "where a kernel's grid is built (once a built forward kernel, once "
       "a built one-kernel backward, twice a built backward pair; heads x "
       "pairs): `computed`, `skipped_causal` "
       "(wholly above the diagonal), `skipped_window` (wholly behind the "
       "window: never fetched) and `skipped_blockdiff` (no visible pair "
       "under the block-diffusion mask: 176 of 256 pairs of 1024-blocks at "
       "8,192 tokens a row). ops/flash_attention.py.",
       label="fate"),
    _m("flash_tiles_total", COUNTER, "1", "training",
       "Tiles (half a block a side) of the `computed` block pairs "
       "of `flash_blocks_total`, counted with them, by what a kernel step "
       "does with them: `unmasked` (every tile of a block no edge of the "
       "mask crosses, and the tiles of an edge block that lie wholly inside "
       "the mask: no iota, compare or select), `masked` (the causal "
       "diagonal or the window's far side crosses the tile: masked element "
       "by element), `skipped` (a tile of an edge block with no visible "
       "pair: neither product nor softmax work) and `whole_edge` (the tiles "
       "of an edge block computed whole and masked: q and k blocks that "
       "differ, a window that is no multiple of the block, a block under "
       "two tiles of 128 lanes). ops/flash_attention.py.",
       label="fate"),
    _m("flash_mask_total", COUNTER, "1", "training",
       "Calls of the flash-attention op, counted where one is traced (a "
       "layer call each), by the mask it was given: `causal` (every key up "
       "to a query's own), `window` (and no further back than the window), "
       "`blockdiff` (the block-diffusion mask over a clean and a noised "
       "copy of a row: `blockdiff_visible`) or `none`. "
       "ops/flash_attention.py.",
       label="mask"),
    _m("train_diffusion_tokens_total", COUNTER, "1", "training",
       "Tokens a block-diffusion language model trained on, summed on the "
       "device inside the train step and added here with each epoch's "
       "loss: `all` every token of every row, `masked` those the step's "
       "noise replaced by the mask id (the only ones with a loss term; "
       "about half under t ~ U(0, 1]). models/transformer.py.",
       label="tokens"),
    _m("train_loop_passes_total", COUNTER, "1", "training",
       "Layer executions of a looped training model's step "
       "(`total_ut_steps` passes of the whole stack on shared weights, one "
       "`lax.scan` in the program: passes x layers), counted once a built "
       "train step by what the loop keeps of each execution for the backward "
       "pass: `recomputed` (`remat_blocks`: the block's input, its flash "
       "kernel's output and row sums and the sub-layer output a second norm "
       "reads, once a pass AND a layer, stacked by the loop) or `plain` "
       "(everything). Absent where the stack runs once. "
       "doc/training.md, a looped model.",
       label="layers"),
    _m("train_exit_mass_total", COUNTER, "1", "training",
       "A looped language model's exit distribution, summed on the device "
       "inside the train step and added here with each epoch's loss: the "
       "sum over a step's positions (those that carry a loss) of the exit "
       "probability p_t of pass t, a label a pass (`1` .. `total_ut_steps`); "
       "the labels sum to train_exit_positions_total. sum_t t * mass_t / "
       "positions is the pass at which the gate expects to stop (1.875 of 4 "
       "at a fresh gate). models/transformer.py.",
       label="pass"),
    _m("train_exit_positions_total", COUNTER, "1", "training",
       "Positions a looped language model's exit distribution was summed "
       "over (train_exit_mass_total): every row's positions but the last, "
       "a padded row's left out."),
    _m("jit_lowerings_total", COUNTER, "1", "training",
       "Programs jax lowered in this process (jaxpr to MLIR: every program "
       "jax compiles, or loads from the persistent compile cache, is lowered "
       "first), counted by profiler.watch_jit_builds from the first fit on. "
       "After warm-up it should stand still: a serving replica or a long fit "
       "that lowers again is rebuilding a program (a new shape, a changed "
       "argument type)."),
    _m("jit_compiles_total", COUNTER, "1", "training",
       "Backend compiles jax asked for in this process, by what the "
       "persistent compile cache did: `hit` (loaded), `miss` (compiled and "
       "written) or `off` (compiled with no cache, or too small an entry to "
       "be kept). Counted with jit_lowerings_total.", label="cache"),
    _m("train_accum_steps", GAUGE, "1", "training",
       "Gradient-accumulation microbatches per optimizer step this fit is "
       "running with (1 = unaccumulated; the RDT_TRAIN_ACCUM_STEPS / "
       "accum_steps= setting after validation)."),
    _m("train_activation_bytes_per_process", GAUGE, "bytes", "training",
       "Compiled peak temporary (activation) bytes of the train step on "
       "this process's devices, read off XLA's memory_analysis — the "
       "activation-residency measure accumulation/remat/seq-sharding "
       "drive down."),
    _m("train_pipeline_stages", GAUGE, "1", "training",
       "Pipeline stages the current fit's GPipe schedule runs over (the "
       "mesh's stage extent; set only when training a PipelineModel — the "
       "accum microbatches double as its pipeline microbatches)."),
]

METRICS: Dict[str, Metric] = {m.name: m for m in _ALL_METRICS}
assert len(METRICS) == len(_ALL_METRICS), "duplicate metric declaration"


def _s(name, subsystem, doc, dynamic=False, kind=PHASE):
    return Span(name=name, subsystem=subsystem, doc=doc, dynamic=dynamic,
                kind=kind)


_ALL_SPANS = [
    # ---- driver -------------------------------------------------------------
    _s("etl:action", "engine",
       "Root span of one engine action (collect/count/cache/materialize/"
       "random-shuffle; the action label rides in args). Mints the "
       "trace_id every downstream span of the action inherits."),
    _s("stage:run", "engine",
       "One stage dispatch: covers submits, retries, speculation, and "
       "lineage-recovery rounds — executor task spans parent here."),
    _s("shuffle:", "engine",
       "Per-stage shuffle totals, one span per wide-op stage "
       "(shuffle:<label>).", dynamic=True),
    _s("aqe:replan", "engine",
       "An adaptive-execution rule re-planned a stage."),
    _s("recover:lineage", "engine",
       "One lineage-recovery rerun of lost producers; links back into the "
       "failing action's trace."),
    _s("speculate:submit", "engine",
       "A speculative backup was submitted for a straggling attempt."),
    _s("speculate:win", "engine",
       "A speculative backup finished before the original attempt."),
    # ---- executor -----------------------------------------------------------
    _s("task:", "executor",
       "One executor task body (task:<SourceType>); child of the driver's "
       "stage:run span across the process boundary.", dynamic=True),
    _s("shuffle:map-partial", "executor",
       "Map-side partial aggregation inside a shuffle map task."),
    _s("shuffle:bucket", "executor",
       "Bucketing a map task's output table."),
    _s("shuffle:write", "executor",
       "Sealing a map task's bucket blobs into the store."),
    _s("shuffle:fetch", "executor",
       "A reduce-side ranged fetch/decode of shuffle input."),
    # ---- serving ------------------------------------------------------------
    _s("serve:predict", "serving",
       "One serving request, enqueue to demuxed completion (driver side); "
       "the batch/hedge/apply spans of its dispatch parent here."),
    _s("serve:batch", "serving",
       "One coalesced micro-batch dispatch to a replica."),
    _s("serve:hedge", "serving",
       "The duplicate dispatch of a hedged micro-batch."),
    _s("serve:apply", "serving",
       "The replica-side jitted apply of one micro-batch."),
    # ---- continuous pipelines -----------------------------------------------
    _s("stream:epoch", "stream",
       "One micro-batch epoch of a continuous pipeline: ingest, transform "
       "action, seal + ledger publish, window partials."),
    _s("stream:window", "stream",
       "One windowed-aggregation merge over the epoch partials of a "
       "closing window (including any replay rounds)."),
    # ---- training: the phases of one fit -------------------------------------
    _s("fit:run", "training",
       "Root span of one FlaxEstimator.fit_on_frame call (estimator, epochs "
       "and batch size ride in args); mints the trace_id every span of the "
       "fit inherits, so the fit's start-up reads as its children."),
    _s("fit:convert", "training",
       "Frame to dataset conversion (estimator.py:_convert_frames); the ETL "
       "action under it parents its etl:action / stage:run / task:* spans "
       "here."),
    _s("fit:shuffle", "training",
       "The random_shuffle pass over the converted dataset before a "
       "streaming fit (one O(dataset) pass through the object store)."),
    _s("fit:feed", "training",
       "Feed set-up of a fit: the residency decision and DeviceEpochCache / "
       "DeviceFeed construction (args: route=resident|stream), and the first "
       "host batch the state is shaped from (args: what=first_batch)."),
    _s("fit:init", "training",
       "State initialisation: model.init as one jitted program, optimizer "
       "state creation and the sharding rules, before placement."),
    _s("train:place", "training",
       "Sharded placement of the train state onto the mesh (host → device "
       "under each leaf's PartitionSpec; covers the initial FSDP/TP scatter "
       "or replication)."),
    _s("train:accum", "training",
       "Before the first dispatch of a fit whose step accumulates over "
       "microbatches or recomputes its blocks (accum > 1 or remat): the "
       "step's lower().compile() and the memory_analysis read behind "
       "train_activation_bytes_per_process; the build's jit:* spans are its "
       "children."),
    _s("train:pipeline", "training",
       "Compilation + activation-residency analysis of the pipelined "
       "(stage-stacked shard_map GPipe) train step — the train:accum twin "
       "for stage>1 fits."),
    _s("train:first_dispatch", "training",
       "The fit's first call of its jitted step program (train step or "
       "resident epoch), the synchronous part of it: the step's one build "
       "(its jit:trace, jit:lower and jit:compile children; every epoch's "
       "accumulators have the types the step returns). A later call that "
       "built the program again (other argument types) would be a jit:* "
       "child of its train:epoch."),
    _s("train:epoch", "training",
       "One epoch of the train loop, loop top to after the callbacks (args: "
       "epoch, steps); epoch 0 holds train:first_dispatch and every other "
       "build (jit:*): the eval step's, the epoch's zeros'."),
    _s("ckpt:save", "training",
       "One checkpoint write (args: step, bytes); children ckpt:import, "
       "ckpt:d2h and ckpt:write."),
    _s("ckpt:import", "training",
       "The lazy `import orbax.checkpoint` of the first save or restore in "
       "a process (seconds on a cold machine, microseconds afterwards)."),
    _s("ckpt:d2h", "training",
       "Device to host copy of the state being saved."),
    _s("ckpt:write", "training",
       "Host state to disk: the orbax (or sharded npz) write, the extra.json "
       "sidecar and retention pruning."),
    # ---- what built a program (profiler.watch_jit_builds) -------------------
    _s("jit:trace", "training",
       "One function traced to a jaxpr (jax's jaxpr_trace_duration; args: "
       "fun), recorded after the fact under the span active on its thread, "
       "at or over profiler.JIT_SPAN_FLOOR_S. A function traced inside "
       "another's trace nests in time, not by parent: read unions."),
    _s("jit:lower", "training",
       "One jaxpr lowered to an MLIR module (jaxpr_to_mlir_module_duration; "
       "args: fun): Pallas kernel bodies are lowered to Mosaic here, in "
       "every run, cache hit or miss."),
    _s("jit:compile", "training",
       "One backend compile or the persistent compile cache's load in its "
       "place (backend_compile_duration; args: fun, cache=hit|miss|off)."),
    # ---- training: step spans (device trace only) ---------------------------
    _s("train:feed_wait", "training",
       "The train loop in next() on the feed: it has no batch to dispatch.",
       kind=STEP),
    _s("train:dispatch", "training",
       "One call of the jitted step program from the train loop (returns "
       "once the work is enqueued; blocks while the device's queue is full).",
       kind=STEP),
    _s("train:epoch_end", "training",
       "Between the last dispatch of an epoch and the end of its callbacks: "
       "loss fetch, report assembly, eval pass, callbacks.", kind=STEP),
    _s("train:loss_fetch", "training",
       "Under train:epoch_end, once an epoch: the fetch of the epoch's summed "
       "loss, where the loop stands until the device has run every program "
       "of the epoch. It closes before any callback runs.", kind=STEP),
    _s("train:report", "training",
       "Under train:epoch_end, once an epoch: the report's assembly, the "
       "fetch of the train metrics' counters and their compute.", kind=STEP),
    _s("train:eval", "training",
       "Under train:epoch_end, once an epoch of a fit with an eval set: the "
       "eval pass and the fetch of its loss and metrics.", kind=STEP),
    _s("train:callbacks", "training",
       "Under train:epoch_end, once an epoch: the estimator's callbacks.",
       kind=STEP),
    _s("train:epoch_turn", "training",
       "From the end of train:epoch_end (for epoch 0, the loop's start) to "
       "the epoch's first train:feed_wait or, resident, its train:dispatch: "
       "the phase span's close and open, the save check and a due save, the "
       "fault probe, the metrics' init, set_epoch, iter(feed).", kind=STEP),
    _s("feed:decode", "feed",
       "One host batch pulled by the feed's host stage (Arrow to numpy, "
       "native staging kernel included).", kind=STEP),
    _s("feed:h2d", "feed",
       "One batch placed on the device(s) by the feed (the device_put call).",
       kind=STEP),
    _s("feed:put_wait", "feed",
       "A feed stage blocked on its full output queue: the stage is ahead "
       "of its consumer.", kind=STEP),
    _s("feed:start", "feed",
       "On the consumer's thread, inside the epoch's first train:feed_wait: "
       "from DeviceFeed.__iter__ (it builds the epoch's chain of stage "
       "threads) to the first placed batch handed out.", kind=STEP),
    _s("feed:stop", "feed",
       "On the consumer's thread, inside the epoch's last train:feed_wait: "
       "the close() of the chain (stop, drain, join each stage's thread).",
       kind=STEP),
    # ---- model: scopes in the device ops' op_name ---------------------------
    _s("attn", "model",
       "A transformer block's attention (`models/transformer.py`): the "
       "projections, QK-norm, RoPE and the flash kernels "
       "(`rdt_flash_fwd`, `rdt_flash_bwd_dkdv_dq`; `rdt_flash_bwd_dkdv` and "
       "`rdt_flash_bwd_dq` where the backward takes two).",
       kind=SCOPE),
    _s("attn_full", "model",
       "Under `attn`: the attention itself (the flash kernels or their jnp "
       "path) of a layer in which a query sees every key up to its own.",
       kind=SCOPE),
    _s("attn_window", "model",
       "Under `attn`: the attention itself of a sliding-window layer (the "
       "kernels `rdt_flash_win_fwd`, `rdt_flash_win_bwd_dkdv_dq`; "
       "`rdt_flash_win_bwd_dkdv` and `rdt_flash_win_bwd_dq` where the "
       "backward takes two).", kind=SCOPE),
    _s("attn_blockdiff", "model",
       "Under `attn`: the attention itself of a layer under the "
       "block-diffusion mask (the kernels `rdt_flash_bd_fwd`, "
       "`rdt_flash_bd_bwd_dkdv_dq`; `rdt_flash_bd_bwd_dkdv` and "
       "`rdt_flash_bd_bwd_dq` where the backward takes two).", kind=SCOPE),
    _s("diffusion", "model",
       "A block-diffusion language model's noise: the draw of one t a block "
       "and one Bernoulli a token, the masked copy of the row, laying out "
       "`[clean ; noised]` and the per-position weights of the loss "
       "(`models/transformer.py`).", kind=SCOPE),
    _s("loop", "model",
       "A looped language model's passes (`total_ut_steps`: one `lax.scan` "
       "over the whole stack on shared weights, forward and transposed). The "
       "layers lie under it by their own names (`block_<i>`); what lies "
       "under it and under no block is the loop's own cost: the stacked "
       "residuals' writes and reads, the carry, the shared weights' "
       "gradients summed pass by pass, and the final norm that ends every "
       "pass (`models/transformer.py`).", kind=SCOPE),
    _s("exit_gate", "model",
       "A looped language model's exit gate: its product with every pass's "
       "hidden states, the sigmoid, the exit distribution, the entropy and "
       "the counts; forward and backward (`models/transformer.py`).",
       kind=SCOPE),
    _s("attn/latent", "model",
       "latent attention's K/V path inside an `attn` scope: the "
       "down-projection to the K/V latent and the one rotary key all heads "
       "share, the latent's norm, the up-projection to every head's keys "
       "and values, RoPE on the two rotary parts, and the broadcast and "
       "concatenation that lay the rotary key into every head's key; "
       "forward, recomputed and backward. The query and output projections "
       "and the flash kernels lie outside it.", kind=SCOPE),
    _s("ssm", "model",
       "every op of a state-space layer's Mamba-2 mixer (module name `ssm`): "
       "its two projections, the convolution, the scan and the gated norm; "
       "forward, recomputed and backward.", kind=SCOPE),
    _s("ssm/in_proj", "model",
       "a state-space mixer's input projection to the gate, `xBC` and `dt`.",
       kind=SCOPE),
    _s("ssm/conv", "model",
       "a state-space mixer's depthwise causal convolution over `xBC` (four "
       "shifted multiply-adds and a bias) and the SiLU after it.",
       kind=SCOPE),
    _s("ssm/scan", "model",
       "a state-space mixer's chunked scan: the kernels `rdt_ssd_fwd` and "
       "`rdt_ssd_bwd` and, outside them, `dt`'s softplus, the decays' "
       "cumulative sums in a chunk, the packing of `dt` for the kernels and "
       "the split of `xBC`.", kind=SCOPE),
    _s("ssm/norm", "model",
       "a state-space mixer's gate (`y * silu(z)`) and the RMSNorm over each "
       "group of channels after it.", kind=SCOPE),
    _s("ssm/out_proj", "model",
       "a state-space mixer's output projection.", kind=SCOPE),
    _s("short_conv", "model",
       "every op of a pair's gated short convolution operator (`layer_kinds` "
       "`C`, module name `short_conv`): its two projections and the gated "
       "convolution between them; forward, recomputed and backward.",
       kind=SCOPE),
    _s("short_conv/in_proj", "model",
       "a convolution operator's input projection to `B`, `C` and `z` "
       "(three widths of the hidden size).", kind=SCOPE),
    _s("short_conv/conv", "model",
       "a convolution operator's stage between its projections, "
       "`C * conv(B * z)`: two gates and a depthwise causal convolution of "
       "a few taps, no bias, no activation (the kernels "
       "`rdt_gated_conv_fwd` and `rdt_gated_conv_bwd`, or the `jax.numpy` "
       "form's passes).", kind=SCOPE),
    _s("short_conv/out_proj", "model",
       "a convolution operator's output projection.", kind=SCOPE),
    _s("kda", "model",
       "every op of a pair's Kimi Delta Attention operator (`layer_kinds` "
       "`K`, module name `kda`): its projections, the convolution, the "
       "gates, the chunked scan and the gated norm; forward, recomputed and "
       "backward.", kind=SCOPE),
    _s("kda/in_proj", "model",
       "a delta-rule operator's ONE fused input projection to q, k and v "
       "(three widths of heads x head_dim).", kind=SCOPE),
    _s("kda/conv", "model",
       "a delta-rule operator's depthwise causal convolution of a few taps "
       "over q, k and v and the SiLU after it (`ssm_glue.conv_silu`: the "
       "kernels `rdt_ssm_conv_fwd` and `rdt_ssm_conv_bwd`, or the "
       "`jax.numpy` form's passes).", kind=SCOPE),
    _s("kda/gate", "model",
       "a delta-rule operator's decay (two low-rank products, the bias, "
       "softplus, times `-exp(A_log)`; float32), `beta` (a projection and a "
       "sigmoid) and the L2 norms of q and k a head.", kind=SCOPE),
    _s("kda/scan", "model",
       "a delta-rule operator's chunked scan (`ops/kda_scan.py`; on a TPU "
       "the kernels `rdt_kda_fwd` and `rdt_kda_bwd`, else the `jax.numpy` "
       "form): the decays' running sums in a chunk, the two score matrices, "
       "the unit-triangular solve, and the walk over the chunks with the state "
       "carried.", kind=SCOPE),
    _s("kda/norm", "model",
       "a delta-rule operator's output stage: the RMSNorm over a head's "
       "channels times the sigmoid of the output gate's two low-rank "
       "products.", kind=SCOPE),
    _s("kda/out_proj", "model",
       "a delta-rule operator's output projection.", kind=SCOPE),
    _s("attn_gate", "model",
       "Under `attn`: the attention output times sigmoid of its gate "
       "projection (`attention_gate`), before the output projection.",
       kind=SCOPE),
    _s("mlp", "model",
       "A transformer block's dense feed-forward (SwiGLU): the three "
       "products, SiLU and the gate.", kind=SCOPE),
    _s("moe/router", "model",
       "Sparse expert layer (`models/moe.py`): float32 router product, "
       "softmax or sigmoid (with its balancing bias), top-k, group sizes "
       "and both auxiliary losses.", kind=SCOPE),
    _s("moe/shared", "model",
       "Sparse expert layer: the shared expert every token takes "
       "(`shared_dim`), its three products, activation and gate.",
       kind=SCOPE),
    _s("moe/dispatch", "model",
       "Sparse expert layer: the sort of the slots by expert and the gather "
       "of the tokens into that order.", kind=SCOPE),
    _s("moe/experts", "model",
       "Sparse expert layer: the grouped expert products (`ragged-dot`), "
       "SiLU and the gate.", kind=SCOPE),
    _s("moe/combine", "model",
       "Sparse expert layer: back to token order and the weighted sum over "
       "a token's experts.", kind=SCOPE),
    _s("lm_head_loss", "model",
       "The language model's head fused into its loss: the head's product, "
       "softmax, cross entropy and both of the head's gradient products, "
       "chunk by chunk in one scan.", kind=SCOPE),
]

SPANS: Dict[str, Span] = {s.name: s for s in _ALL_SPANS}
assert len(SPANS) == len(_ALL_SPANS), "duplicate span declaration"

#: exact names literal ``profiler.trace(...)`` calls may use (the linter's
#: check set); dynamic families are prefixes of runtime-formatted names
SPAN_NAMES = frozenset(s.name for s in _ALL_SPANS
                       if not s.dynamic and s.kind != SCOPE)
#: the subset ``profiler.step`` takes (and ``trace``/``open_span`` must not)
STEP_SPAN_NAMES = frozenset(s.name for s in _ALL_SPANS if s.kind == STEP)
SPAN_PREFIXES = tuple(s.name for s in _ALL_SPANS if s.dynamic)
#: the model scopes a device trace's ops are attributed to
SCOPE_NAMES = frozenset(s.name for s in _ALL_SPANS if s.kind == SCOPE)


def _e(kind, subsystem, doc):
    return Event(kind=kind, subsystem=subsystem, doc=doc)


_ALL_EVENTS = [
    _e("fault_injected", "faults",
       "A fault-injection rule fired (site, key, action) — recorded in the "
       "process where the fault executed."),
    _e("object_lost", "store",
       "An ObjectLostError was raised (object id + detail) — the read-side "
       "view of a store loss."),
    _e("recovery_round", "recovery",
       "The engine re-executed producers for lost blobs (stage, producer "
       "and blob counts)."),
    _e("stream_reseal", "shuffle",
       "A regenerated map re-sealed its publication under the next "
       "generation."),
    _e("executor_down", "scheduler",
       "Task placement marked an executor unreachable."),
    _e("executor_up", "scheduler",
       "A down-marked executor answered again and re-entered task "
       "placement (restart re-admission; the executor_down symmetry)."),
    _e("executor_drain", "scheduler",
       "An executor began a graceful drain out of the pool (deliberate "
       "retirement, never a crash)."),
    _e("pool_scale", "scheduler",
       "The autoscale controller grew or shrank the executor pool "
       "(direction + resulting size)."),
    _e("warm_fork", "scheduler",
       "A worker spawn went through (or degraded out of) the warm-fork "
       "plane: forked pid, or the failure that fell back to cold spawn."),
    _e("store_budget", "store",
       "Per-host store budgets were re-derived from the AQE plane's "
       "measured stage bytes (or the derivation degraded to the static "
       "budgets on an injected store.budget fault)."),
    _e("store_fault_in", "store",
       "A spilled payload was faulted back into shared memory on read "
       "(object id + host)."),
    _e("stage_abort", "scheduler",
       "A failing stage ran the abort contract (drain + free)."),
    _e("admission_reject", "scheduler",
       "An action parked at admission timed out (RDT_ADMIT_TIMEOUT_S) and "
       "failed with the typed no-retry AdmissionRejected."),
    _e("backpressure", "scheduler",
       "Dispatch to a host paused on the store high-watermark, or resumed "
       "below the low-watermark (memory backpressure transitions)."),
    _e("action_failed", "engine",
       "An engine action surfaced a StageError; a blackbox bundle is "
       "written alongside."),
    _e("replica_down", "serving",
       "A serving replica left the rotation (connection lost or "
       "ReplicaNotLoaded)."),
    _e("replica_up", "serving",
       "A serving replica reloaded and rejoined the rotation."),
    _e("hedge", "serving",
       "A dispatch was hedged onto a second replica."),
    _e("request_failed", "serving",
       "A serving request failed on every replica within the re-route "
       "grace (ServingError)."),
    _e("overload_shed", "serving",
       "A serving request was refused at admission (ServingOverloaded) "
       "because the session's outstanding queue was at its bound."),
    _e("hot_swap", "serving",
       "A serving session atomically shifted traffic to a freshly loaded "
       "servable version (the old one retires in the background)."),
    _e("unload_failed", "serving",
       "A retired replica refused serve_unload through the whole retry "
       "window — its servable's weights stay pinned in that executor "
       "process (loud leak record: replica, executor, version, error)."),
    _e("rollout_promote", "serving",
       "A guarded rollout ramped its canary to full weight healthy and "
       "promoted it to primary through the swap/retire machinery."),
    _e("rollout_rollback", "serving",
       "A guarded rollout auto-rolled-back: the canary judged unhealthy "
       "(error-rate or p99 vs baseline) or the rollout timed out — "
       "weight to 0, canary unloaded, blackbox bundle written with the "
       "failing step's numbers."),
    _e("serve_scale", "serving",
       "The serving autoscaler changed (or failed to change) the "
       "per-version replica count (direction, replicas, reason)."),
    _e("stream_replay", "stream",
       "A continuous pipeline re-derived a lost epoch blob from its "
       "source journal (exactly-once replay; epoch + reason recorded)."),
]

EVENTS: Dict[str, Event] = {e.kind: e for e in _ALL_EVENTS}
assert len(EVENTS) == len(_ALL_EVENTS), "duplicate event declaration"


# ---- process-local state -----------------------------------------------------

_lock = threading.Lock()
_counters: Dict[str, Dict[str, float]] = {}  # guarded-by: _lock
_gauges: Dict[str, Dict[str, float]] = {}    # guarded-by: _lock
_hists: Dict[str, Dict[str, Dict[str, Any]]] = {}  # guarded-by: _lock
_events: Optional[collections.deque] = None  # guarded-by: _lock
_events_dropped = 0                          # guarded-by: _lock


def _event_cap() -> int:
    """The flight-recorder ring bound — read lazily so this module stays
    stdlib-only at import (the knob registry itself imports the package)."""
    try:
        from raydp_tpu import knobs
        return max(16, int(knobs.get("RDT_FLIGHT_MAX_EVENTS")))
    except Exception:  # noqa: BLE001 - standalone load (linter), bootstrap
        return 1024


def _metric(name: str, kind: str) -> Metric:
    m = METRICS[name]  # unknown name must fail loudly, same as knobs.get
    if m.kind != kind:
        raise ValueError(f"metric {name} is a {m.kind}, not a {kind}")
    return m


def inc(name: str, value: float = 1, label: str = "") -> None:
    """Add to a counter (cheap: one lock + dict update)."""
    _metric(name, COUNTER)
    with _lock:
        by_label = _counters.setdefault(name, {})
        by_label[label] = by_label.get(label, 0) + value


def set_gauge(name: str, value: float, label: str = "") -> None:
    _metric(name, GAUGE)
    with _lock:
        _gauges.setdefault(name, {})[label] = value


def observe(name: str, value: float, label: str = "") -> None:
    """Record one observation into a summary-shaped histogram."""
    _metric(name, HISTOGRAM)
    with _lock:
        h = _hists.setdefault(name, {}).setdefault(label, dict(_HIST_ZERO))
        h["count"] += 1
        h["sum"] += value
        h["min"] = value if h["min"] is None else min(h["min"], value)
        h["max"] = value if h["max"] is None else max(h["max"], value)


def record_event(kind: str, **fields) -> None:
    """Append one structured event to the bounded flight-recorder ring."""
    global _events, _events_dropped
    EVENTS[kind]  # unknown kind must fail loudly
    ev = {"ts": time.time(), "kind": kind}
    ev.update(fields)
    dropped = False
    with _lock:
        if _events is None:
            _events = collections.deque(maxlen=_event_cap())
        if len(_events) == _events.maxlen:
            _events_dropped += 1
            dropped = True
        _events.append(ev)
    if dropped:
        inc("flightrec_events_dropped_total")  # outside _lock: inc takes it


def events() -> List[Dict[str, Any]]:
    with _lock:
        return list(_events) if _events is not None else []


def snapshot() -> Dict[str, Any]:
    """This process's metric state: ``{"counters": {name: {label: v}},
    "gauges": ..., "hists": {name: {label: {count,sum,min,max}}}}``."""
    with _lock:
        return {
            "counters": {n: dict(d) for n, d in _counters.items()},
            "gauges": {n: dict(d) for n, d in _gauges.items()},
            "hists": {n: {lb: dict(h) for lb, h in d.items()}
                      for n, d in _hists.items()},
        }


def export_state() -> Dict[str, Any]:
    """The ``__rdt_metrics__`` intrinsic payload: metrics + the flight
    recorder ring + this process's wall clock (for offset alignment)."""
    with _lock:
        evs = list(_events) if _events is not None else []
        dropped = _events_dropped
    return {"metrics": snapshot(), "events": evs,
            "events_dropped": dropped, "clock_ns": time.time_ns(),
            "pid": os.getpid()}


def reset() -> None:
    """Wipe all process-local metric and event state (tests)."""
    global _events, _events_dropped
    with _lock:
        _counters.clear()
        _gauges.clear()
        _hists.clear()
        _events = None
        _events_dropped = 0


# ---- merging -----------------------------------------------------------------

def merge_snapshots(snaps: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge per-process snapshots: counters and histogram components sum;
    gauges sum too (each process contributes its own level — per-process
    values stay readable under ``processes`` in :func:`metrics_report`)."""
    out = {"counters": {}, "gauges": {}, "hists": {}}
    for snap in snaps:
        for name, by_label in (snap.get("counters") or {}).items():
            tgt = out["counters"].setdefault(name, {})
            for lb, v in by_label.items():
                tgt[lb] = tgt.get(lb, 0) + v
        for name, by_label in (snap.get("gauges") or {}).items():
            tgt = out["gauges"].setdefault(name, {})
            for lb, v in by_label.items():
                tgt[lb] = tgt.get(lb, 0) + v
        for name, by_label in (snap.get("hists") or {}).items():
            tgt = out["hists"].setdefault(name, {})
            for lb, h in by_label.items():
                t = tgt.setdefault(lb, dict(_HIST_ZERO))
                t["count"] += h.get("count", 0)
                t["sum"] += h.get("sum", 0.0)
                for k, fn in (("min", min), ("max", max)):
                    v = h.get(k)
                    if v is not None:
                        t[k] = v if t[k] is None else fn(t[k], v)
    return out


def _collect_process_states(timeout: float = 10.0):
    """(states, skipped): every reachable process's ``export_state()`` —
    the driver itself, live actors via the ``__rdt_metrics__`` intrinsic,
    and node agents via their ``telemetry`` RPC."""
    states: Dict[str, Dict[str, Any]] = {"driver": export_state()}
    skipped = 0
    try:
        from raydp_tpu.runtime import head as head_mod
        if not head_mod.runtime_initialized():
            return states, skipped
        rt = head_mod.get_runtime()
        from raydp_tpu.runtime.actor import ActorHandle
        for aid, rec in list(rt.records.items()):
            if rec.state != "ALIVE":
                continue
            if not rec.ready.is_set():
                skipped += 1  # mid-restart: never park on the ready grace
                continue
            role = rec.spec.name or aid
            try:
                handle = ActorHandle(aid, rec.spec.name, rt.server.address)
                states[role] = handle.call("__rdt_metrics__",
                                           timeout=timeout)
            except Exception:  # noqa: BLE001 - a dying actor is skipped,
                skipped += 1   # counted, and reported — never silent
        for node_id, agent in list(getattr(rt, "node_agents", {}).items()):
            try:
                # metrics_state, NOT telemetry: the latter ships the whole
                # span ring, which this harvest would discard (and a
                # blackbox bundle would embed verbatim)
                states[f"agent-{node_id}"] = agent.call("metrics_state",
                                                        timeout=timeout)
            except Exception:  # noqa: BLE001 - same skip contract
                skipped += 1
    except Exception:  # noqa: BLE001 - no runtime: the driver state stands
        pass
    if skipped:
        inc("telemetry_skipped_processes_total", skipped)
        states["driver"] = export_state()  # re-snapshot with the skip count
    return states, skipped


def metrics_report(include_actors: bool = True) -> Dict[str, Any]:
    """The merged cross-process metrics view: ``merged`` (counters/hists
    summed, gauges summed), ``processes`` (role → that process's metrics),
    and ``skipped_processes`` (unreachable lanes — nonzero means the merge
    is incomplete). Subsumes the legacy per-subsystem reports:
    ``store_ops_total`` is ``op_counts()``, the ``serve_*`` counters are
    ``serving_report()``'s, the scheduler/recovery counters are the
    ``shuffle_stage_report`` columns."""
    if include_actors:
        states, skipped = _collect_process_states()
    else:
        states, skipped = {"driver": export_state()}, 0
    procs = {role: st.get("metrics", {}) for role, st in states.items()}
    return {"merged": merge_snapshots(list(procs.values())),
            "processes": procs,
            "skipped_processes": skipped}


# ---- prometheus / json dumps -------------------------------------------------

def _prom_name(name: str) -> str:
    return "rdt_" + name


def render_prometheus(merged: Dict[str, Any]) -> str:
    """Prometheus text exposition of one merged snapshot (histograms render
    as summary-style ``_count``/``_sum`` plus ``_max``)."""
    lines: List[str] = []

    def _sample(pname, label_name, label, value):
        tag = f'{{{label_name}="{label}"}}' if label else ""
        lines.append(f"{pname}{tag} {value}")

    for m in _ALL_METRICS:
        pname = _prom_name(m.name)
        if m.kind == COUNTER:
            data = merged.get("counters", {}).get(m.name)
        elif m.kind == GAUGE:
            data = merged.get("gauges", {}).get(m.name)
        else:
            data = merged.get("hists", {}).get(m.name)
        if not data:
            continue
        lines.append(f"# HELP {pname} {m.doc}")
        lines.append(f"# TYPE {pname} "
                     f"{'summary' if m.kind == HISTOGRAM else m.kind}")
        for lb in sorted(data):
            if m.kind == HISTOGRAM:
                h = data[lb]
                _sample(pname + "_count", m.label, lb, h["count"])
                _sample(pname + "_sum", m.label, lb, h["sum"])
                if h["max"] is not None:
                    _sample(pname + "_max", m.label, lb, h["max"])
            else:
                _sample(pname, m.label, lb, data[lb])
    return "\n".join(lines) + "\n"


def dump(out_dir: Optional[str] = None) -> Dict[str, str]:
    """Write the merged report as ``metrics.json`` + ``metrics.prom`` into
    ``out_dir`` (default: ``<session_dir>/metrics``); returns the paths."""
    if out_dir is None:
        out_dir = os.path.join(session_dir(), "metrics")
    os.makedirs(out_dir, exist_ok=True)
    report = metrics_report()
    json_path = os.path.join(out_dir, "metrics.json")
    prom_path = os.path.join(out_dir, "metrics.prom")
    with open(json_path, "w") as fh:
        json.dump(report, fh, indent=2, default=str)
    with open(prom_path, "w") as fh:
        fh.write(render_prometheus(report["merged"]))
    return {"json": json_path, "prom": prom_path}


def session_dir() -> str:
    """Where telemetry files go: the live session's directory, else
    ``<tempdir>/raydp_tpu`` (the platform's temp dir, so ``TMPDIR`` moves it)."""
    try:
        from raydp_tpu.runtime import head as head_mod
        if head_mod.runtime_initialized():
            return head_mod.get_runtime().session_dir
    except Exception:  # noqa: BLE001 - no runtime: the default dir stands
        pass
    import tempfile
    return os.path.join(tempfile.gettempdir(), "raydp_tpu")


# ---- flight-recorder blackbox bundles ---------------------------------------

#: bundles written per action label this session — a chaos storm failing the
#: same action in a loop must not fill the disk with identical postmortems
_BLACKBOX_CAP_PER_ACTION = 5
_blackbox_counts: Dict[str, int] = {}  # guarded-by: _lock


def write_blackbox(action: str, error: Optional[BaseException] = None,
                   extra: Optional[Dict[str, Any]] = None) -> Optional[str]:
    """Harvest every reachable process's flight-recorder ring (plus its
    metrics snapshot) into ``<session_dir>/blackbox/blackbox-<action>[-n]
    .json``; returns the path (None past the per-action cap). Called by the
    engine when an action surfaces ``StageError`` and by the serving
    session on ``ServingError`` — best-effort by contract: a failed harvest
    must never mask the error that triggered it."""
    safe = "".join(c if c.isalnum() or c in "-_" else "-" for c in action)
    with _lock:
        n = _blackbox_counts.get(safe, 0)
        if n >= _BLACKBOX_CAP_PER_ACTION:
            return None
        _blackbox_counts[safe] = n + 1
    states, skipped = _collect_process_states()
    bundle = {
        "action": action,
        "ts": time.time(),
        "error": None if error is None else str(error),
        "exc_type": None if error is None else type(error).__name__,
        "skipped_processes": skipped,
        "processes": states,
    }
    if extra:
        bundle["extra"] = extra
    out_dir = os.path.join(session_dir(), "blackbox")
    os.makedirs(out_dir, exist_ok=True)
    suffix = "" if n == 0 else f"-{n}"
    path = os.path.join(out_dir, f"blackbox-{safe}{suffix}.json")
    with open(path, "w") as fh:
        json.dump(bundle, fh, indent=2, default=str)
    return path


# ---- generated doc tables ----------------------------------------------------

def generate_table(tag: str) -> str:
    """Markdown table for one registry (``spans`` / ``metrics`` /
    ``events``). The blocks between ``rdtlint:telemetry-table`` markers in
    ``doc/observability.md`` are exactly this output; rule
    ``telemetry-registry`` fails on any drift."""
    if tag == "metrics":
        lines = ["| Metric | Kind | Unit | Label | Subsystem | Description |",
                 "| --- | --- | --- | --- | --- | --- |"]
        for m in _ALL_METRICS:
            lines.append(
                f"| `{m.name}` | {m.kind} | {m.unit} | "
                f"{('`' + m.label + '`') if m.label else '—'} | "
                f"{m.subsystem} | {m.doc} |")
    elif tag == "spans":
        lines = ["| Span | Class | Subsystem | Description |",
                 "| --- | --- | --- | --- |"]
        for s in _ALL_SPANS:
            name = f"`{s.name}…` *(dynamic)*" if s.dynamic else f"`{s.name}`"
            lines.append(f"| {name} | {s.kind} | {s.subsystem} | {s.doc} |")
    elif tag == "events":
        lines = ["| Event | Subsystem | Description |",
                 "| --- | --- | --- |"]
        for e in _ALL_EVENTS:
            lines.append(f"| `{e.kind}` | {e.subsystem} | {e.doc} |")
    else:
        raise ValueError(f"unknown telemetry table {tag!r}")
    return "\n".join(lines)


DOC_FILE = "doc/observability.md"
DOC_TAGS = ("spans", "metrics", "events")

_BEGIN = "<!-- rdtlint:telemetry-table:begin {tag} -->"
_END = "<!-- rdtlint:telemetry-table:end -->"


def table_markers(tag: str) -> tuple:
    return _BEGIN.format(tag=tag), _END


def render_block(tag: str) -> str:
    begin, end = table_markers(tag)
    return f"{begin}\n{generate_table(tag)}\n{end}"


def write_doc_tables(root: str) -> list:
    """Rewrite the telemetry table blocks in ``doc/observability.md`` from
    the registries; returns the files changed."""
    path = os.path.join(root, DOC_FILE)
    if not os.path.exists(path):
        return []
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    orig = text
    for tag in DOC_TAGS:
        begin, end = table_markers(tag)
        if begin not in text or end not in text:
            continue
        head, rest = text.split(begin, 1)
        _, tail = rest.split(end, 1)
        text = head + render_block(tag) + tail
    if text != orig:
        with open(path, "w", encoding="utf-8") as f:
            f.write(text)
        return [DOC_FILE]
    return []


def main(argv: Optional[list] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m raydp_tpu.metrics",
        description="print or regenerate the telemetry registry tables")
    ap.add_argument("--write-docs", action="store_true",
                    help="rewrite the generated doc tables in place")
    ap.add_argument("--root", default=".",
                    help="repo root holding doc/ (default: cwd)")
    args = ap.parse_args(argv)
    if args.write_docs:
        changed = write_doc_tables(args.root)
        for rel in changed:
            print(f"rewrote {rel}")
        if not changed:
            print("telemetry tables already fresh")
        return 0
    for tag in DOC_TAGS:
        print(f"## {tag}\n{generate_table(tag)}\n")
    return 0


if __name__ == "__main__":  # pragma: no cover - thin CLI shim
    raise SystemExit(main())
