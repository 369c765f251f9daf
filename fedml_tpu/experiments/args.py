"""Shared argparse flags — parity with the reference's experiment mains.

Reference flag set: fedml_experiments/distributed/fedavg/main_fedavg.py:48-117
(model/dataset/data_dir/partition_method/partition_alpha/client_num_in_total/
client_num_per_round/batch_size/client_optimizer/backend/lr/wd/epochs/
comm_round/frequency_of_the_test/ci...), plus per-algorithm extras added by
each main (fedopt's server_optimizer/server_lr main_fedopt.py:54-60, robust's
defense flags main_fedavg_robust.py:56-63). ``--backend`` values are the
TPU-era execution paths instead of MPI/GRPC/MQTT transports.
"""

from __future__ import annotations

import argparse


def add_federated_args(parser: argparse.ArgumentParser):
    parser.add_argument("--model", type=str, default=None,
                        help="model name (default: dataset's reference pick)")
    parser.add_argument("--dataset", type=str, default="blob")
    parser.add_argument("--data_dir", type=str, default="")
    parser.add_argument("--partition_method", type=str, default="hetero",
                        choices=["homo", "hetero", "hetero-fix"])
    parser.add_argument("--partition_alpha", type=float, default=0.5)
    parser.add_argument("--client_num_in_total", type=int, default=10)
    parser.add_argument("--client_num_per_round", type=int, default=10)
    parser.add_argument("--batch_size", type=int, default=32)
    parser.add_argument("--client_optimizer", type=str, default="sgd")
    parser.add_argument("--backend", type=str, default="simulation",
                        choices=["simulation", "spmd", "inproc", "tcp",
                                 "grpc"],
                        help="simulation: vmapped single-program; spmd: "
                             "device-mesh round; inproc/tcp/grpc: "
                             "cross-silo actor protocol")
    parser.add_argument("--lr", type=float, default=0.03)
    parser.add_argument("--wd", type=float, default=0.0)
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--comm_round", type=int, default=10)
    parser.add_argument("--frequency_of_the_test", type=int, default=5)
    parser.add_argument("--compute_dtype", type=str, default=None,
                        choices=[None, "bfloat16", "float32"],
                        help="mixed precision: forward/backward dtype "
                             "(masters stay f32)")
    parser.add_argument("--accum_steps", type=int, default=1,
                        help="average grads over k micro-batches per "
                             "optimizer step (effective batch = "
                             "k * batch_size, one micro-batch of HBM)")
    parser.add_argument("--lr_decay_round", type=float, default=1.0,
                        help="per-round exponential client-LR decay: "
                             "effective lr at round r is lr * decay**r "
                             "(1.0 = the reference's constant lr)")
    parser.add_argument("--model_parallel", type=str, default=None,
                        choices=[None, "tp", "fsdp"],
                        help="spmd backend: shard the model over a second "
                             "mesh axis inside each client slot — tp "
                             "(Megatron, transformer models) or fsdp "
                             "(ZeRO-3, any model)")
    parser.add_argument("--mp_size", type=int, default=1,
                        help="devices per client slot for --model_parallel")
    parser.add_argument("--mesh_shape", type=str, default=None,
                        help="spmd backend: named data x fsdp x tp "
                             "federation mesh, e.g. 'data=4,fsdp=2' — "
                             "sampled clients ride the data axis while "
                             "every client's model carries the canonical "
                             "SpecLayout fsdp/tp parameter layout "
                             "(parallel/mesh.py); supersedes "
                             "--model_parallel/--mp_size")
    parser.add_argument("--prefetch_depth", type=int, default=2,
                        help="async round pipeline: pack + upload the "
                             "next round's cohort (or fused block window) "
                             "on a background thread while the current "
                             "round runs on device, holding at most this "
                             "many cohorts in flight (2 = double "
                             "buffering). 0 = serial host loop; "
                             "$FEDML_TPU_PREFETCH overrides. Trajectories "
                             "are bit-identical either way.")
    parser.add_argument("--fused_rounds", type=int, default=0,
                        help="throughput mode (simulation backend): run N "
                             "rounds per device dispatch under one "
                             "lax.scan; partial cohorts sample on device "
                             "(jax RNG, not the np.random host contract)")
    parser.add_argument("--eval_train_subsample", type=int, default=None,
                        help="evaluate train metrics on a fixed seeded "
                             "subsample of the train union (None = full)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--run_dir", type=str, default="./runs/latest")
    parser.add_argument("--profile_dir", type=str, default=None,
                        help="write a TensorBoard-loadable jax.profiler "
                             "trace of the training loop here")
    parser.add_argument("--obs_dir", type=str, default=None,
                        help="federation flight recorder (fedml_tpu/obs): "
                             "per-round telemetry timelines to "
                             "flight_rank<r>.jsonl under this directory, "
                             "per-silo digest rows, and anomaly-armed "
                             "one-shot jax.profiler windows under "
                             "<obs_dir>/profiles. Merge N logs with "
                             "`python -m fedml_tpu.obs merge <obs_dir>`. "
                             "Pure observer: trajectories are bit-exact "
                             "vs unset (the default: off)")
    parser.add_argument("--job_id", type=str, default=None,
                        help="flight-record correlation id stamped on "
                             "every telemetry record (default: a "
                             "per-driver constant) — lets one obs_dir "
                             "hold several jobs' logs")
    parser.add_argument("--serve_port", type=int, default=None,
                        help="federated serving tier (fedml_tpu/serve, "
                             "--algo fedavg_cross_silo): hot-swap every "
                             "round's aggregated model into a jitted, "
                             "batch-coalescing TCP/JSON inference "
                             "endpoint on this port (0 = ephemeral) that "
                             "serves round r while r+1 trains. Pure "
                             "observer: trajectories are bit-exact vs "
                             "unset (the default: no serving)")
    parser.add_argument("--serve_staleness_rounds", type=int, default=2,
                        help="serving staleness bound: replies lagging "
                             "the newest trained round by more than this "
                             "many rounds are flagged stale (the "
                             "endpoint keeps serving its last good "
                             "model either way — a bounded-stale answer "
                             "beats a refused one)")
    parser.add_argument("--use_wandb", action="store_true")
    parser.add_argument("--checkpoint_dir", type=str, default=None)
    parser.add_argument("--resume", action="store_true")
    parser.add_argument("--compression", type=str, default=None,
                        help="cross-silo wire policy: none | delta_int8 | "
                             "topk_ef | topk_ef_int8 (append :frac for the "
                             "top-k keep fraction, e.g. topk_ef_int8:0.05). "
                             "Compresses BOTH directions: uplink deltas "
                             "(with error feedback for top-k) and downlink "
                             "broadcasts against the silo mirror. "
                             "$FEDML_TPU_COMPRESSION overrides. FedAsync "
                             "warns and stays full precision.")
    parser.add_argument("--compress", action="store_true",
                        help="deprecated: the exact pre-policy behavior "
                             "(uplink int8 model-update deltas only, "
                             "full-precision broadcasts) — use "
                             "--compression for the bidirectional stack")
    # -- fault tolerance (cross-silo actor backends) ------------------------
    parser.add_argument("--round_deadline_s", type=float, default=None,
                        help="cross-silo fault tolerance: close a round "
                             "with a weighted PARTIAL aggregate once this "
                             "deadline passes with >= min_quorum_frac of "
                             "live silos reported, evicting the "
                             "non-reporters (they rejoin via JOIN + a "
                             "full-precision resync). Unset = the strict "
                             "all-received barrier. Also the per-round "
                             "deadline of --algo fedavg_async quorum mode "
                             "(its default there is 10).")
    parser.add_argument("--min_quorum_frac", type=float, default=0.5,
                        help="fraction of LIVE silos that must report "
                             "before a deadline close may evict the rest "
                             "(below it the deadline extends instead)")
    parser.add_argument("--heartbeat_s", type=float, default=0.0,
                        help="silo heartbeat period (0 = off): idle silos "
                             "beat the server's liveness table, and after "
                             "~3 silent beats send JOIN to re-admit "
                             "themselves (evicted or restarted silos)")
    parser.add_argument("--fault_plan", type=str, default=None,
                        help="seeded chaos harness (comm/faults.py): a "
                             "DSL string like "
                             "'seed=7;drop:p=0.1;delay:p=0.2,delay_ms=50', "
                             "inline JSON, or a .json path. Wraps every "
                             "comm endpoint; empty/unset = no injection")
    # -- elastic control plane (fedml_tpu/control/) --------------------------
    parser.add_argument("--server_checkpoint_dir", type=str, default=None,
                        help="durable server control-plane snapshots + "
                             "round/cohort ledger: the full round-schedule "
                             "state (round index, live set, compression "
                             "mirror, pending replies, steering windows) "
                             "is written atomically at round boundaries "
                             "and deadline closes, so a killed-and-"
                             "restarted server resumes mid-schedule. "
                             "Snapshots write ASYNCHRONOUSLY by default "
                             "(dedicated writer thread, newest-wins "
                             "coalescing, group-committed ledger fsyncs); "
                             "see --checkpoint_sync. Unset = no snapshots "
                             "(legacy)")
    parser.add_argument("--checkpoint_sync", action="store_true",
                        help="force SYNCHRONOUS control-plane snapshots: "
                             "serialize+fsync+publish inline on the round "
                             "thread at every boundary, one ledger fsync "
                             "per line (the pre-async semantics — "
                             "recovery point is always the latest "
                             "boundary, at round-critical-path cost). "
                             "Default off = async writer thread; restore "
                             "may land a few rounds back and replay "
                             "forward to the identical ledger")
    parser.add_argument("--pace_steering", action="store_true",
                        help="adaptive pace steering (Bonawitz et al.): "
                             "derive each round's deadline (p90 of "
                             "observed report latencies x1.5, clamped to "
                             "[base/4, base*4]) and quorum target from "
                             "the straggler distribution instead of the "
                             "static flags; --round_deadline_s is the "
                             "base/fallback and --min_quorum_frac the "
                             "floor. Off = byte-identical static "
                             "schedule")
    parser.add_argument("--join_rate_limit", type=float, default=0.0,
                        help="JOIN admission control: token-bucket rate "
                             "(joins/sec) on the server's full-precision "
                             "rejoin-resync path; throttled silos get a "
                             "BACKPRESSURE reply with retry_after_s so a "
                             "mass rejoin after a partition cannot "
                             "stampede the server. 0 = off")
    parser.add_argument("--max_deadline_extensions", type=int, default=25,
                        help="cap on consecutive below-quorum deadline "
                             "extensions per round; exhausting it raises "
                             "a loud SchedulingStallError (final state "
                             "checkpointed) instead of extending forever. "
                             "Negative = unbounded (the legacy behavior)")
    # -- WAN-realistic federation (fedml_tpu/wan/) ---------------------------
    parser.add_argument("--wan_trace", type=str, default=None,
                        help="WAN world model (--algo fedavg_cross_silo): "
                             "a seeded diurnal availability trace driving "
                             "churn through the real protocol — cohorts "
                             "sample only currently-available clients, "
                             "trace-offline silos drop replies and get "
                             "deadline-evicted, rejoin is trace-gated "
                             "through JOIN + admission. DSL like "
                             "'seed=7;period_s=960;peak=0.95;trough=0.5;"
                             "flap=180:120:0.5', inline JSON, or a .json "
                             "path (see README 'WAN-realistic "
                             "federation'). Unset = off")
    parser.add_argument("--wan_profiles", type=str, default=None,
                        help="heterogeneous client profiles for the WAN "
                             "world: per-client compute (lognormal) and "
                             "up/downlink bandwidth (Pareto) as pure "
                             "functions of (seed, client id), injected as "
                             "report delays the pace steerer must track. "
                             "DSL like 'compute_median_s=0.1;"
                             "compute_sigma=0.8;bw_alpha=1.5'. Requires "
                             "--wan_trace")
    parser.add_argument("--wan_round_s", type=float, default=60.0,
                        help="WAN virtual clock: simulated seconds per "
                             "federation round (round r happens at sim "
                             "time r * wan_round_s — the trace never "
                             "reads the wall clock, so a churn run "
                             "replays bit-identically under one seed)")
    # -- population virtualization (fedml_tpu/state/) -----------------------
    parser.add_argument("--population", type=int, default=None,
                        help="virtualize the client population at this "
                             "size: overrides --client_num_in_total and "
                             "routes per-client shards through the "
                             "tiered client-state store, so host memory "
                             "is O(cohort + cache) instead of "
                             "O(population). Datasets 'virtual_powerlaw' "
                             "and 'store' honor it natively; resident "
                             "loaders just get the bigger client count.")
    parser.add_argument("--state_dir", type=str, default=None,
                        help="client-state store directory (shard files "
                             "for per-client state: EF residuals, data "
                             "indices, streamed corpora). Unset = the "
                             "RAM-only LRU tier (generative datasets) / "
                             "checkpoint_dir-derived silo state.")
    parser.add_argument("--state_cache_clients", type=int, default=4096,
                        help="client-state store LRU budget, in clients: "
                             "how many clients' shards stay resident in "
                             "host RAM before write-back/eviction — the "
                             "knob that bounds RSS at population scale")
    parser.add_argument("--ci", type=int, default=0,
                        help="1 = tiny smoke-run truncation (reference --ci)")
    return parser


def resolve_max_extensions(args):
    """Flag convention shared by every launcher: a negative
    ``--max_deadline_extensions`` means unbounded (the pre-control-plane
    forever-extend behavior), encoded as None for the server managers."""
    v = getattr(args, "max_deadline_extensions", 25)
    return None if v is not None and v < 0 else v


def build_dataset_and_model(args):
    """Registry-driven load_data + create_model (the reference's per-main
    load_data/create_model pair, main_fedavg.py:120-266)."""
    from fedml_tpu.data.registry import (DEFAULT_MODEL_AND_TASK, load_data)
    from fedml_tpu.models import create_model

    client_num = args.client_num_in_total
    if getattr(args, "population", None):
        # the population flag IS the client count — and because every
        # sampler above VIRTUAL_SAMPLE_THRESHOLD draws O(cohort), it can
        # be 10^6 without the host ever materializing per-client arrays
        client_num = args.population
    ds = load_data(args.dataset, args.data_dir,
                   partition_method=args.partition_method,
                   partition_alpha=args.partition_alpha,
                   client_num_in_total=client_num,
                   state_dir=getattr(args, "state_dir", None),
                   state_cache_clients=getattr(args, "state_cache_clients",
                                               None))
    if args.dataset not in DEFAULT_MODEL_AND_TASK and not args.model:
        import logging
        logging.warning("no reference model pairing for dataset %r; "
                        "defaulting to lr (pass --model to override)",
                        args.dataset)
    model_name, task = DEFAULT_MODEL_AND_TASK.get(
        args.dataset, ("lr", "classification"))
    if args.model:
        model_name = args.model
    model = create_model(model_name, output_dim=ds.class_num)
    return ds, model, task
