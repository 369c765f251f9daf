"""FedAvg experiment main — all execution backends behind one CLI.

Parity: fedml_experiments/{standalone,distributed}/fedavg/main_fedavg.py
merged into one entry point selected by ``--backend``:
- simulation  -> FedAvgAPI (vmapped round; the standalone paradigm)
- spmd        -> DistributedFedAvgAPI over a device mesh (the distributed
                 paradigm, collectives instead of messages)
- inproc/tcp/grpc -> cross-silo actor protocol over the message layer

Usage (CI smoke): python -m fedml_tpu.experiments.main_fedavg \
    --dataset blob --comm_round 3 --client_num_in_total 4 --ci 1
"""

from __future__ import annotations

import argparse
import logging

from fedml_tpu.experiments.args import (add_federated_args,
                                        build_dataset_and_model,
                                        resolve_max_extensions)
from fedml_tpu.trainer.functional import TrainConfig
from fedml_tpu.utils.checkpoint import CheckpointManager
from fedml_tpu.utils.metrics import MetricsSink


def make_train_config(args) -> TrainConfig:
    return TrainConfig(epochs=args.epochs, batch_size=args.batch_size,
                       lr=args.lr, client_optimizer=args.client_optimizer,
                       wd=args.wd,
                       compute_dtype=getattr(args, "compute_dtype", None),
                       accum_steps=getattr(args, "accum_steps", 1),
                       lr_decay_round=getattr(args, "lr_decay_round", 1.0))


def run_simulation(args, ds, model, task, sink):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig

    cfg = FedAvgConfig(comm_round=args.comm_round,
                       client_num_per_round=args.client_num_per_round,
                       frequency_of_the_test=args.frequency_of_the_test,
                       seed=args.seed,
                       eval_train_subsample=getattr(
                           args, "eval_train_subsample", None),
                       prefetch_depth=getattr(args, "prefetch_depth", 2),
                       obs_dir=getattr(args, "obs_dir", None),
                       job_id=getattr(args, "job_id", None),
                       train=make_train_config(args))
    api = FedAvgAPI(ds, model, task=task, config=cfg)
    if getattr(args, "fused_rounds", 0):
        # throughput mode: up to N rounds per device dispatch
        # (FusedRounds). Partial cohorts run in block mode — host-presampled
        # with the host loop's exact sampling stream, packed at the block's
        # cohort bucket — so the trajectory equals the host loop's.
        if args.checkpoint_dir:
            logging.warning("--checkpoint_dir is not wired for "
                            "--fused_rounds; ignoring")
        fused = api.fused_rounds()
        rec = fused.train(max_rounds_per_dispatch=args.fused_rounds)
        for hist_rec in api.history:
            sink.log(hist_rec, step=hist_rec["round"])
        return rec
    mgr = (CheckpointManager(args.checkpoint_dir)
           if args.checkpoint_dir else None)
    start = 0
    if mgr and args.resume:
        restored = mgr.restore_latest({"variables": api.variables})
        if restored:
            state, meta = restored
            api.variables = state["variables"]
            start = meta["round_idx"]
            logging.info("resumed from round %d", start)
    rec = {}
    for r in range(start, cfg.comm_round):
        api.run_round(r)
        if r % cfg.frequency_of_the_test == 0 or r == cfg.comm_round - 1:
            rec = api.evaluate(r)
            sink.log(rec, step=r)
        if mgr:
            mgr.save(r + 1, {"variables": api.variables})
    return rec


def run_spmd(args, ds, model, task, sink):
    from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                         DistributedFedAvgConfig)

    mesh_shape = getattr(args, "mesh_shape", None)
    if mesh_shape:
        from fedml_tpu.parallel.mesh import parse_mesh_shape
        mesh_shape = parse_mesh_shape(mesh_shape)
    cfg = DistributedFedAvgConfig(
        comm_round=args.comm_round,
        client_num_per_round=args.client_num_per_round,
        frequency_of_the_test=args.frequency_of_the_test, seed=args.seed,
        model_parallel=getattr(args, "model_parallel", None),
        mp_size=getattr(args, "mp_size", 1),
        mesh_shape=mesh_shape,
        prefetch_depth=getattr(args, "prefetch_depth", 2),
        obs_dir=getattr(args, "obs_dir", None),
        job_id=getattr(args, "job_id", None),
        train=make_train_config(args))
    api = DistributedFedAvgAPI(ds, model, task=task, config=cfg)
    if getattr(args, "fused_rounds", 0) and cfg.model_parallel:
        logging.warning("--fused_rounds supports the flat 'clients' mesh "
                        "only; --model_parallel run uses the per-round "
                        "host loop")
    if getattr(args, "fused_rounds", 0) and not cfg.model_parallel:
        # throughput mode on the mesh: sampled cohorts run as host-drawn
        # fused blocks, full participation as federation-resident scans
        if args.checkpoint_dir:
            logging.warning("--checkpoint_dir is not wired for "
                            "--fused_rounds; ignoring")
        final = api.train_fused(max_rounds_per_dispatch=args.fused_rounds)
        for rec in api.history:
            sink.log(rec, step=rec["round"])
        return final
    mgr = (CheckpointManager(args.checkpoint_dir)
           if args.checkpoint_dir else None)
    final = api.train(checkpoint_mgr=mgr, resume=args.resume)
    for rec in api.history:
        sink.log(rec, step=rec["round"])
    return final


def run_cross_silo(args, ds, model, task, sink):
    from fedml_tpu.algorithms.fedavg_cross_silo import run_fedavg_cross_silo

    addresses = None
    if args.backend in ("tcp", "grpc"):
        addresses = {r: ("127.0.0.1", 29500 + r)
                     for r in range(args.client_num_per_round + 1)}
    _, history = run_fedavg_cross_silo(
        ds, model, task=task, worker_num=args.client_num_per_round,
        comm_round=args.comm_round, train_cfg=make_train_config(args),
        backend=args.backend, addresses=addresses,
        compress=getattr(args, "compress", False),
        compression=getattr(args, "compression", None),
        checkpoint_dir=args.checkpoint_dir, resume=args.resume,
        prefetch_depth=getattr(args, "prefetch_depth", 2),
        round_deadline_s=getattr(args, "round_deadline_s", None),
        min_quorum_frac=getattr(args, "min_quorum_frac", 0.5),
        heartbeat_s=getattr(args, "heartbeat_s", 0.0),
        fault_plan=getattr(args, "fault_plan", None),
        # elastic control plane (fedml_tpu/control/)
        server_checkpoint_dir=getattr(args, "server_checkpoint_dir", None),
        checkpoint_sync=getattr(args, "checkpoint_sync", False),
        pace_steering=getattr(args, "pace_steering", False),
        join_rate_limit=getattr(args, "join_rate_limit", 0.0),
        max_deadline_extensions=resolve_max_extensions(args),
        # federation flight recorder (fedml_tpu/obs)
        obs_dir=getattr(args, "obs_dir", None),
        job_id=getattr(args, "job_id", None),
        # fedopt-style server step when the launcher passes the fedopt flags
        server_optimizer=getattr(args, "cross_silo_server_optimizer", None),
        server_lr=getattr(args, "server_lr", 1e-3))
    for rec in history:
        sink.log(rec, step=rec["round"])
    return history[-1] if history else {}


def apply_ci_truncation(args):
    """--ci 1 = smoke-run truncation (the reference threads --ci into
    trainers to cut evaluation short, FedAVGAggregator.py:126-131; here we
    clamp the round/participant counts, which bounds the whole run)."""
    if getattr(args, "ci", 0):
        args.comm_round = min(args.comm_round, 2)
        args.client_num_per_round = min(args.client_num_per_round, 4)
        args.frequency_of_the_test = 1
    return args


# shared with fed_launch so the two entry points cannot drift
BACKEND_RUNNERS = {"simulation": run_simulation, "spmd": run_spmd,
                   "inproc": run_cross_silo, "tcp": run_cross_silo,
                   "grpc": run_cross_silo}


def main(argv=None):
    from fedml_tpu.utils import enable_persistent_compilation_cache
    parser = argparse.ArgumentParser("fedml_tpu fedavg")
    add_federated_args(parser)
    args = apply_ci_truncation(parser.parse_args(argv))
    enable_persistent_compilation_cache()
    logging.basicConfig(level=logging.INFO)
    ds, model, task = build_dataset_and_model(args)
    sink = MetricsSink(args.run_dir, config=vars(args),
                       use_wandb=args.use_wandb)
    from fedml_tpu.utils.tracing import profile
    with profile(getattr(args, "profile_dir", None)):
        final = BACKEND_RUNNERS[args.backend](args, ds, model, task, sink)
    sink.finish()
    logging.info("final: %s", final)
    return final


if __name__ == "__main__":
    main()
