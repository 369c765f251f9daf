"""The documented metric registry — every RoundTimer name, machine-checked.

``RoundTimer``'s phase/counter/gauge maps are ``defaultdict``s: a typo'd
name at a ``timer.count(...)`` call site silently creates a NEW key and
the intended series simply stops moving — the evidence rows look healthy
while measuring nothing. This registry is the single source of truth for
every metric name the tree may emit:

- lint rule FT017 (``analysis/rules/metrics_names.py``) rejects any
  ``timer.count/add/gauge/phase`` call whose LITERAL name is not
  registered here, and rejects a registered name missing from the README
  "Observability" metric table — the registry doubles as the
  machine-checked README table, the same conformance pattern FT016 uses
  for launcher flags;
- the flight recorder and the merge tool treat these names as the
  per-round timeline's schema (unknown keys still round-trip — the
  registry constrains what the TREE emits, not what a log may carry).

Adding a metric is a two-line change: one row here, one row in the
README table. FT017 fails CI until both exist.
"""

from __future__ import annotations

from typing import Dict

#: metric kinds: how RoundTimer aggregates the series
KIND_PHASE = "phase"      # wall-clock totals + call counts (timer.phase/add)
KIND_COUNTER = "counter"  # monotone event counts (timer.count)
KIND_GAUGE = "gauge"      # high-water marks, max-aggregated (timer.gauge)
#: fields of the per-round ``perf`` flight record (obs/perf.py) — derived
#: from a closed round's deltas, not a RoundTimer series; registered here
#: so FT017 pins the names the same way it pins the timer's
KIND_DERIVED = "derived"


def _m(kind: str, subsystem: str, meaning: str) -> Dict[str, str]:
    return {"kind": kind, "subsystem": subsystem, "meaning": meaning}


#: name -> {kind, subsystem, meaning}. Sorted by family, then name.
METRICS: Dict[str, Dict[str, str]] = {
    # -- round phases (drivers: fedavg sim, mesh/SPMD, fused) --------------
    "pack": _m(KIND_PHASE, "round pipeline",
               "host-side cohort pack (pad-and-mask shard assembly)"),
    "upload": _m(KIND_PHASE, "round pipeline",
                 "H2D transfer of the packed cohort: its enqueue, and for "
                 "a cohort whose host buffers are recycled (4 MB and up) "
                 "the wait until the transfers have read them"),
    "dispatch": _m(KIND_PHASE, "round pipeline",
                   "device round dispatch (async enqueue of the jitted "
                   "round program)"),
    "device_wait": _m(KIND_PHASE, "round pipeline",
                      "eval-boundary drain of pending device compute"),
    "eval": _m(KIND_PHASE, "round pipeline",
               "global train/test union evaluation"),
    "prefetch_wait": _m(KIND_PHASE, "prefetch",
                        "caller time blocked on an in-flight prefetch "
                        "slot (pack latency NOT hidden by the pipeline)"),
    "produce": _m(KIND_PHASE, "round pipeline",
                  "the whole host side of one round's inputs as the "
                  "prefetch worker (or the serial path) runs it: "
                  "sampling, pack, upload, per-client keys; produce - "
                  "pack - upload is the unaccounted rest"),
    "device_starved": _m(KIND_PHASE, "round pipeline",
                         "host-input half of a round (up to dispatch) "
                         "that began with the device already idle: a "
                         "lower bound on the device time the host cost"),
    "device_starved_max": _m(KIND_PHASE, "round pipeline",
                             "host-input half of a round during which "
                             "the device ran dry; device_starved + this "
                             "is the upper bound"),
    "starved_rounds": _m(KIND_COUNTER, "round pipeline",
                         "rounds whose host-input half left the device "
                         "idle (charged to device_starved or "
                         "device_starved_max)"),
    "rows_dispatched": _m(KIND_COUNTER, "round pipeline",
                          "rows every dispatched round program steps "
                          "through, padding included: client slots x padded "
                          "length (mesh padding too), or where the sim "
                          "driver runs a ragged cohort in tiers the sum "
                          "over tiers of clients x the longest client's "
                          "batches x batch size"),
    "tokens_dispatched": _m(KIND_COUNTER, "round pipeline",
                            "where a row is a sequence of token ids: "
                            "rows_dispatched x the row's positions, added "
                            "at every dispatch of the sim driver"),
    "agg_kernel_params": _m(KIND_COUNTER, "round pipeline",
                            "parameters of the model whose stacked mean the "
                            "sim driver's Pallas kernel takes (leaves the "
                            "TPU tiles without padding), counted once when "
                            "the driver is built on a TPU"),
    "agg_xla_params": _m(KIND_COUNTER, "round pipeline",
                         "the rest of the model (vectors, narrow matrices), "
                         "whose stacked mean is left to XLA; with "
                         "agg_kernel_params the model's parameter count"),
    "conv_dead_tap_params": _m(KIND_COUNTER, "round pipeline",
                               "parameters of the model in convolution "
                               "taps that only ever meet zero padding at "
                               "the federation's row shape, which "
                               "LiveTapConv slices out of the local step "
                               "(they stay in the model and its mean); "
                               "counted once when the driver is built"),
    "local_carried_params": _m(KIND_COUNTER, "round pipeline",
                               "parameters a client's local loop carries "
                               "through its steps: the model's count less "
                               "the dead taps (conv_dead_tap_params) where "
                               "the client optimizer leaves a zero gradient "
                               "alone; counted once when the driver is "
                               "built"),
    "clients_folded": _m(KIND_COUNTER, "round pipeline",
                         "clients a folded round (FedAvgConfig.fold_clients) "
                         "trained one after another and folded into the "
                         "running sum, added at every dispatch"),
    "clients_first_step_out_of_place": _m(
        KIND_COUNTER, "round pipeline",
        "clients of a folded round whose first local step read the global "
        "model's leaves and wrote the client's own (local_train's "
        "shared_init, which make_folded_body passes), so no leaf was "
        "copied to start the client; added at every dispatch, absent where "
        "the cohort trains under a vmap"),
    # -- prefetch counters (parallel/prefetch.py) --------------------------
    "prefetch_hit": _m(KIND_COUNTER, "prefetch",
                       "round consumed a speculatively packed cohort"),
    "prefetch_miss": _m(KIND_COUNTER, "prefetch",
                        "round packed inline (cold start / misprediction "
                        "/ dataset swap)"),
    "pack_buffers_recycled": _m(KIND_COUNTER, "prefetch",
                                "cohorts packed into host buffers a round "
                                "before them had used "
                                "(FedAvgAPI._pack_cohort's pool: no page of "
                                "them has to be faulted in again)"),
    "pack_buffers_fresh": _m(KIND_COUNTER, "prefetch",
                             "cohorts packed into newly allocated host "
                             "buffers: the pool had none of that shape "
                             "(first rounds, a new padded length or "
                             "dataset, after release_prefetch), or the "
                             "cohort is under the native packer's 4 MB "
                             "floor"),
    # -- wire accounting (comm backends via launch_federation) -------------
    "comm_bytes_up": _m(KIND_COUNTER, "comm",
                        "client->server wire bytes, actual encoded frame "
                        "lengths"),
    "comm_bytes_down": _m(KIND_COUNTER, "comm",
                          "server->client wire bytes, actual encoded "
                          "frame lengths"),
    # -- server round hot path (serialize-once broadcast + streaming fold) -
    "bcast_fanout_ms": _m(KIND_GAUGE, "comm",
                          "slowest round-open broadcast fan-out: wall "
                          "time from first enqueue to the round thread "
                          "regaining control (NOT wire drain — the "
                          "per-peer writer threads absorb slow links)"),
    "send_queue_depth": _m(KIND_GAUGE, "comm",
                           "peak per-peer send-queue depth observed at "
                           "broadcast enqueue (bounded queue; overflow "
                           "sheds the peer through the eviction path)"),
    "codec_encode_ms": _m(KIND_GAUGE, "comm",
                          "slowest downlink compression encode (top-k/"
                          "EF select + quantize + mirror advance) on "
                          "the round thread before a broadcast"),
    "agg_fold_ms": _m(KIND_GAUGE, "round pipeline",
                      "slowest streaming-fold step (decode + in-order "
                      "prefix fold of one reply, or the round-close "
                      "drain of the out-of-order buffer)"),
    "agg_buffered_peak": _m(KIND_GAUGE, "round pipeline",
                            "peak out-of-order reply buffer size held by "
                            "the streaming aggregator (contiguous-prefix "
                            "replies fold immediately and never buffer)"),
    # -- fault tolerance (PR-5 layer; rolled up by launch_federation) ------
    "ft_retries": _m(KIND_COUNTER, "fault tolerance",
                     "transport send retries across every endpoint"),
    "ft_dedup_drops": _m(KIND_COUNTER, "fault tolerance",
                         "duplicate frames shed by receive-side "
                         "[epoch, seq] dedup"),
    "ft_conn_errors": _m(KIND_COUNTER, "fault tolerance",
                         "connection-level errors observed by the "
                         "transports"),
    "ft_faults_injected": _m(KIND_COUNTER, "fault tolerance",
                             "chaos-harness faults injected "
                             "(comm/faults.py)"),
    "ft_evictions": _m(KIND_COUNTER, "fault tolerance",
                       "silos evicted from the live set (deadline miss "
                       "or send failure)"),
    "ft_rejoins": _m(KIND_COUNTER, "fault tolerance",
                     "silos re-admitted to the live set (JOIN or a live "
                     "reply)"),
    "ft_partial_rounds": _m(KIND_COUNTER, "fault tolerance",
                            "rounds closed with a weighted partial "
                            "aggregate"),
    "ft_stale_replies": _m(KIND_COUNTER, "fault tolerance",
                           "replies for an already-closed round, "
                           "discarded"),
    "ft_corrupt_frames": _m(KIND_COUNTER, "fault tolerance",
                            "replies that failed payload decode and were "
                            "dropped"),
    "ft_join_resyncs": _m(KIND_COUNTER, "fault tolerance",
                          "full-precision mirror resyncs sent to "
                          "rejoining silos"),
    "ft_heartbeats": _m(KIND_COUNTER, "fault tolerance",
                        "heartbeat messages the server processed"),
    "ft_deadline_extensions": _m(KIND_COUNTER, "fault tolerance",
                                 "below-quorum deadline extensions"),
    # -- elastic control plane (PR-7 layer) --------------------------------
    "cp_checkpoints": _m(KIND_COUNTER, "control plane",
                         "server control-state snapshots saved"),
    "cp_restores": _m(KIND_COUNTER, "control plane",
                      "server control-state restores (failover resumes)"),
    "cp_deadline_adjustments": _m(KIND_COUNTER, "control plane",
                                  "pace-steering deadline/quorum changes"),
    "cp_joins_throttled": _m(KIND_COUNTER, "control plane",
                             "JOINs rejected with BACKPRESSURE by "
                             "admission control"),
    "cp_steered_deadline_s": _m(KIND_GAUGE, "control plane",
                                "largest pace-steered round deadline"),
    "cp_resync_latency_skips": _m(KIND_COUNTER, "control plane",
                                  "rejoin-resync reply latencies excluded "
                                  "from the pace-steering window (they "
                                  "measure the outage, not the silo's "
                                  "pace — the churn-poisoning guard)"),
    "cp_capture_ms": _m(KIND_GAUGE, "control plane",
                        "slowest control-state capture (the host-copy "
                        "cost the round thread pays per snapshot — with "
                        "the async writer this IS the round thread's "
                        "whole checkpoint bill)"),
    "cp_flush_ms": _m(KIND_GAUGE, "control plane",
                      "slowest snapshot serialize+fsync+publish (inline "
                      "in --checkpoint_sync mode; the writer thread's "
                      "last completed flush in async mode)"),
    "cp_writer_queue_coalesced": _m(KIND_COUNTER, "control plane",
                                    "snapshots replaced in the async "
                                    "writer's depth-1 newest-wins slot "
                                    "before publishing (backpressure: "
                                    "the writer fell behind the round "
                                    "cadence)"),
    "cp_fsync_total": _m(KIND_COUNTER, "control plane",
                         "every fsync the control-plane checkpointer "
                         "issued over the run (blobs, sidecars, "
                         "directory entries, ledger), folded into the "
                         "timer after the close barrier"),
    "cp_ledger_fsyncs": _m(KIND_COUNTER, "control plane",
                           "ledger.jsonl group-commit fsyncs (subset "
                           "of cp_fsync_total; one per N-line/T-ms "
                           "batch plus the flush-on-close tail)"),
    # -- WAN world model (fedml_tpu/wan/) -----------------------------------
    "wan_cohort_rejections": _m(KIND_COUNTER, "wan",
                                "cohort-draw candidates skipped because "
                                "the availability trace marked them "
                                "offline"),
    "wan_forced_cohorts": _m(KIND_COUNTER, "wan",
                             "cohort slots filled from the unrestricted "
                             "stream because the available population "
                             "was exhausted (graceful degradation, "
                             "never a stall)"),
    "wan_offline_drops": _m(KIND_COUNTER, "wan",
                            "broadcasts a silo dropped because its "
                            "embodied device was trace-offline (no "
                            "training, no reply — the deadline eviction "
                            "path removes it)"),
    "wan_delay_injected_ms": _m(KIND_COUNTER, "wan",
                                "total injected report delay across the "
                                "fleet (the heterogeneous straggler "
                                "profiles), milliseconds"),
    "wan_join_deferred": _m(KIND_COUNTER, "wan",
                            "JOINs answered with BACKPRESSURE because "
                            "the silo's device was still trace-offline "
                            "(the deterministic rejoin gate)"),
    "wan_mass_joins": _m(KIND_COUNTER, "wan",
                         "estimated population-scale device arrivals "
                         "per round (the trace's churn wave, "
                         "sample-scaled)"),
    "wan_mass_leaves": _m(KIND_COUNTER, "wan",
                          "estimated population-scale device departures "
                          "per round"),
    "wan_mass_join_throttled": _m(KIND_COUNTER, "wan",
                                  "population JOIN-wave arrivals the "
                                  "shadow admission bucket (same rate as "
                                  "--join_rate_limit, sim clock) would "
                                  "have throttled"),
    "wan_available_frac": _m(KIND_GAUGE, "wan",
                             "highest per-round population availability "
                             "fraction observed (the per-round "
                             "trajectory rides the round records' "
                             "wan_available_frac field)"),
    # -- federation scheduler (fedml_tpu/sched/) ---------------------------
    "sched_device_time": _m(KIND_PHASE, "scheduler",
                            "wall-clock this job held the shared device "
                            "gate (fair-share accounting; solo runs "
                            "without a gate emit none)"),
    "sched_gate_wait": _m(KIND_PHASE, "scheduler",
                          "wall-clock this job's actors queued for a "
                          "device slot behind co-tenants (contention "
                          "visibility per tenant)"),
    "sched_device_acquires": _m(KIND_COUNTER, "scheduler",
                                "device-gate grants to this job "
                                "(deficit-round-robin turns taken)"),
    "sched_unrouted_frames": _m(KIND_COUNTER, "scheduler",
                                "frames arriving at a shared fabric "
                                "endpoint for a job not running there "
                                "(counted on the physical endpoint, "
                                "dropped)"),
    # -- federated serving tier (fedml_tpu/serve/) -------------------------
    "serve_requests": _m(KIND_COUNTER, "serving",
                         "predict requests accepted by the batch "
                         "coalescer (shed requests count too — they "
                         "entered the submit path)"),
    "serve_batches": _m(KIND_COUNTER, "serving",
                        "coalesced batches dispatched to the warmed "
                        "predict program"),
    "serve_shed": _m(KIND_COUNTER, "serving",
                     "requests rejected by load shedding (full bounded "
                     "queue or a deadline that died in the queue — the "
                     "429 analogue)"),
    "serve_swap_ms": _m(KIND_GAUGE, "serving",
                        "slowest hot-swap (async device_put + atomic "
                        "reference flip) installing a round's model "
                        "into the endpoint; the first install's "
                        "bucket-ladder compile is excluded (one-off)"),
    "serve_p50_ms": _m(KIND_GAUGE, "serving",
                       "median request latency (submit to reply) over "
                       "the coalescer's bounded window, high-watered"),
    "serve_p99_ms": _m(KIND_GAUGE, "serving",
                       "p99 request latency over the coalescer's "
                       "bounded window, high-watered"),
    "serve_staleness_rounds": _m(KIND_GAUGE, "serving",
                                 "largest trained-vs-serving round gap "
                                 "observed (the staleness bound's "
                                 "measured counterpart)"),
    # -- tiered client-state store (state/store.py) ------------------------
    "state_cache_hits": _m(KIND_COUNTER, "state store",
                           "shard reads served from the resident LRU"),
    "state_cache_misses": _m(KIND_COUNTER, "state store",
                             "shard reads that faulted in from disk / "
                             "the generator"),
    "state_evictions": _m(KIND_COUNTER, "state store",
                          "shards evicted from the resident LRU"),
    "state_bytes_read": _m(KIND_COUNTER, "state store",
                           "bytes faulted in from disk shards"),
    "state_bytes_written": _m(KIND_COUNTER, "state store",
                              "bytes spilled to disk shards"),
    # -- host ---------------------------------------------------------------
    "host_rss_peak_mb": _m(KIND_GAUGE, "host",
                           "peak resident set size of this process (MB)"),
    # -- observability (fedml_tpu/obs/) -------------------------------------
    "obs_anomalies": _m(KIND_COUNTER, "observability",
                        "anomaly records written to the flight log "
                        "(slow round / stall / deadline extension); "
                        "per-round attribution rides the anomaly "
                        "record's own round field — a slow-round bump "
                        "lands after end_round, i.e. in the next "
                        "round's counter delta"),
    "obs_profiled_rounds": _m(KIND_COUNTER, "observability",
                              "rounds captured by an anomaly-armed "
                              "one-shot jax.profiler window (bumped at "
                              "the window's close, so the delta lands "
                              "in the following round's record)"),
    "obs_fsync_batches": _m(KIND_COUNTER, "observability",
                            "flight-recorder group-commit fsyncs (one "
                            "per batch of sync-worthy round/anomaly "
                            "records — N lines or T ms, whichever "
                            "first); credited after end_round, so the "
                            "delta lands in the following round's "
                            "record"),
    # -- perf flight deck (obs/perf.py): per-round derived perf record ------
    "mfu": _m(KIND_DERIVED, "perf",
              "model FLOP utilization: achieved FLOP/s over the fleet "
              "bf16 peak (documented per-device table x device count; "
              "$FEDML_TPU_PEAK_FLOPS overrides the per-device figure); "
              "omitted on CPU/unknown devices"),
    "achieved_flops_per_s": _m(KIND_DERIVED, "perf",
                               "round program FLOPs (analytic jaxpr cost "
                               "model) over the measured round duration"),
    "comm_compute_overlap_frac": _m(KIND_DERIVED, "perf",
                                    "fraction of host pack+upload hidden "
                                    "behind device compute by the round "
                                    "pipeline (prefetch-hit rounds: "
                                    "1 - prefetch_wait/(pack+upload); "
                                    "serial rounds read 0)"),
    "wire_bytes_per_sec_up": _m(KIND_DERIVED, "perf",
                                "client->server wire throughput this "
                                "round (encoded frame bytes / duration)"),
    "wire_bytes_per_sec_down": _m(KIND_DERIVED, "perf",
                                  "server->client wire throughput this "
                                  "round (encoded frame bytes / "
                                  "duration)"),
    "device_mem_peak_mb": _m(KIND_GAUGE, "perf",
                             "peak device (HBM) bytes in use across "
                             "local devices, MB — best-effort "
                             "memory_stats(); omitted where the backend "
                             "exposes none (CPU)"),
    "device_mem_in_use_mb": _m(KIND_DERIVED, "perf",
                               "current device bytes in use across local "
                               "devices, MB at round close — best-effort "
                               "memory_stats(); omitted where the "
                               "backend exposes none (CPU)"),
}


def metric_names() -> frozenset:
    """Every registered metric name — the FT017 allow set."""
    return frozenset(METRICS)


def markdown_table() -> str:
    """The registry as a GitHub markdown table (the README section's
    generator — regenerate with ``python -m fedml_tpu.obs registry``)."""
    rows = ["| metric | kind | subsystem | meaning |",
            "|---|---|---|---|"]
    for name in sorted(METRICS):
        m = METRICS[name]
        rows.append(f"| `{name}` | {m['kind']} | {m['subsystem']} | "
                    f"{m['meaning']} |")
    return "\n".join(rows)
