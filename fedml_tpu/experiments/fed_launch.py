"""Generic multi-algorithm launcher (reference fed_launch: a single main
that dispatches any algorithm — fedml_experiments/distributed/fed_launch/).

``python -m fedml_tpu.experiments.fed_launch --algo fedopt --dataset blob``

Each algorithm adds its own flags on top of the shared federated set.
"""

from __future__ import annotations

import argparse
import logging

from fedml_tpu.experiments.args import (add_federated_args,
                                        build_dataset_and_model,
                                        resolve_max_extensions)
from fedml_tpu.experiments.main_fedavg import make_train_config
from fedml_tpu.utils.metrics import MetricsSink

# every algorithm family dispatches end-to-end from the generic flags;
# split_nn uses a dense bottom/top cut and vertical_fl an even feature-column
# split across --party_num parties (their APIs take arbitrary splits)
ALGOS = ["fedavg", "fedavg_cross_silo", "fedopt", "fednova",
         "fedavg_robust", "hierarchical",
         "decentralized", "centralized", "fednas", "fedgkt",
         "turboaggregate", "fedseg", "split_nn", "vertical_fl",
         "contribution", "fedavg_async"]


def add_algo_args(parser: argparse.ArgumentParser):
    # fedopt (main_fedopt.py:54-60)
    parser.add_argument("--server_optimizer", type=str, default="adam")
    parser.add_argument("--server_lr", type=float, default=1e-3)
    parser.add_argument("--server_momentum", type=float, default=0.0)
    # fednova
    parser.add_argument("--gmf", type=float, default=0.0)
    parser.add_argument("--prox_mu", type=float, default=0.0)
    # robust (main_fedavg_robust.py:56-63; median/trimmed_mean/krum are
    # Byzantine-robust aggregation rules beyond the reference pair)
    from fedml_tpu.core.robust import ROBUST_AGGREGATORS
    parser.add_argument("--defense_type", type=str,
                        default="norm_diff_clipping",
                        choices=["norm_diff_clipping", "weak_dp", "none",
                                 *sorted(ROBUST_AGGREGATORS)])
    parser.add_argument("--norm_bound", type=float, default=5.0)
    parser.add_argument("--stddev", type=float, default=0.025)
    parser.add_argument("--trim_ratio", type=float, default=0.1)
    parser.add_argument("--num_byzantine", type=int, default=1)
    parser.add_argument("--multi_m", type=int, default=1)
    # reference poisoned artifacts (edge_case_examples/data_loader.py:283):
    # path-based ingestion of the shipped southwest/ardis pickles; the
    # attacker client's local set becomes the reference's clean+edge mix
    # and accuracy on the edge test set is reported as backdoor_asr
    parser.add_argument("--poison_pkl", type=str, default=None,
                        help="reference-format poisoned train artifact "
                             "(.pkl southwest stack or .pt torch dataset). "
                             "TRUSTED PATHS ONLY: pickle/legacy torch.load "
                             "execute arbitrary code from the file")
    parser.add_argument("--poison_test_pkl", type=str, default=None,
                        help="edge-case test artifact for the attack-"
                             "success-rate metric (same trust caveat as "
                             "--poison_pkl)")
    parser.add_argument("--attacker_client", type=int, default=0)
    parser.add_argument("--target_label", type=int, default=9)
    parser.add_argument("--poison_num_edge", type=int, default=100)
    parser.add_argument("--poison_num_clean", type=int, default=400)
    # hierarchical (group_num = edge servers)
    parser.add_argument("--group_num", type=int, default=2)
    parser.add_argument("--group_comm_round", type=int, default=2)
    # fedgkt (main_fedgkt.py)
    parser.add_argument("--epochs_client", type=int, default=1)
    parser.add_argument("--epochs_server", type=int, default=1)
    parser.add_argument("--pretrained_path", type=str, default=None,
                        help="torch .pth mirroring the GKT client model; "
                             "warm-starts every client feature extractor")
    parser.add_argument("--alpha", type=float, default=1.0)
    parser.add_argument("--temperature", type=float, default=1.0)
    # decentralized online (main_decentralized_fl args)
    parser.add_argument("--mode", type=str, default="DOL",
                        choices=["DOL", "PUSHSUM"])
    parser.add_argument("--topology_neighbors_num_undirected", type=int,
                        default=4)
    # fednas (main_fednas: --arch_learning_rate; --nas_variant gdas =
    # gumbel-softmax single-path search; --arch_unrolled = 2nd order)
    parser.add_argument("--arch_lr", type=float, default=3e-4)
    parser.add_argument("--nas_variant", type=str, default="darts",
                        choices=["darts", "gdas"])
    parser.add_argument("--arch_unrolled", action="store_true")
    parser.add_argument("--nas_retrain_rounds", type=int, default=0,
                        help="after the search, FedAvg-train the derived "
                             "genotype network for N rounds (reference "
                             "search->train workflow)")
    # turboaggregate
    parser.add_argument("--frac_bits", type=int, default=16)
    # vertical_fl (guest = party 0 with labels + first feature block)
    parser.add_argument("--party_num", type=int, default=3)
    # fedseg (reference SegmentationLosses / LR_Scheduler knobs)
    parser.add_argument("--seg_loss", type=str, default="ce",
                        choices=["ce", "focal"])
    # fedavg_async (straggler tolerance — beyond the reference, whose
    # server hard-blocks on the all-received barrier)
    parser.add_argument("--async_mode", type=str, default="quorum",
                        choices=["quorum", "fedasync"],
                        help="quorum: close rounds at (all | deadline & "
                             "quorum); fedasync: merge every update with "
                             "a staleness-decayed weight")
    # --round_deadline_s moved to the shared federated flags (args.py):
    # it now drives BOTH the quorum server and the cross-silo
    # deadline-eviction path; quorum mode defaults to 10.0 when unset
    parser.add_argument("--quorum", type=int, default=1)
    parser.add_argument("--async_alpha", type=float, default=0.6)
    parser.add_argument("--async_poly_a", type=float, default=0.5)
    parser.add_argument("--max_updates", type=int, default=20,
                        help="fedasync: total update budget (the async "
                             "analogue of --comm_round)")


def _log_history(api, sink, fused_rounds: int = 0):
    """Run api.train() — or, when ``--fused_rounds`` is set and the API
    has a fused driver, the scan-chunked FusedRounds.train() (host sync
    once per eval interval). APIs without a fusable round (host-side
    stages, non-FedAvg-family loops) fall back to the host loop with a
    warning rather than failing the run."""
    if fused_rounds:
        try:
            # block mode: partial cohorts host-presampled with the host
            # loop's sampling stream — trajectory-identical to api.train()
            driver = api.fused_rounds()
        except (AttributeError, TypeError, ValueError) as exc:
            logging.warning("--fused_rounds unsupported for %s (%s); "
                            "using the host loop",
                            type(api).__name__, exc)
        else:
            # the flag's value is the dispatch cap: N rounds per device
            # call, eval cadence unchanged (ADVICE r3)
            final = driver.train(max_rounds_per_dispatch=fused_rounds)
            for rec in getattr(api, "history", []):
                sink.log(rec, step=rec.get("round"))
            sink.finish()
            logging.info("final: %s", final)
            return final
    final = api.train()
    for rec in getattr(api, "history", []):
        sink.log(rec, step=rec.get("round"))
    sink.finish()
    logging.info("final: %s", final)
    return final


# algorithms whose inner loop does not consume TrainConfig's optimizer
# factory — flags like --accum_steps don't reach them
_CUSTOM_LOOP_ALGOS = {"fednova", "decentralized", "split_nn", "vertical_fl",
                      "fednas", "fedgkt"}


def _validate_before_sink(args, ds):
    """Shape/flag checks that should reject BEFORE a metrics run (possibly
    wandb) is opened."""
    if args.algo in ("split_nn", "vertical_fl"):
        if ds.train_data_global[0].ndim != 2:
            raise SystemExit(
                f"{args.algo}'s generic wiring needs flat features "
                f"(e.g. --dataset blob); {args.dataset!r} samples have "
                f"shape {ds.train_data_global[0].shape[1:]}")
    if args.algo == "vertical_fl":
        dim = ds.train_data_global[0].shape[1]
        if not 0 < args.party_num <= dim:
            raise SystemExit(
                f"--party_num {args.party_num} must be in [1, {dim}] "
                f"(the feature dimension of {args.dataset!r})")
    if args.accum_steps > 1 and args.algo in _CUSTOM_LOOP_ALGOS:
        logging.warning("--accum_steps is only wired for TrainConfig-based "
                        "algorithms; ignoring for %r", args.algo)
    if getattr(args, "serve_port", None) is not None \
            and args.algo != "fedavg_cross_silo":
        logging.warning("--serve_port is only wired for --algo "
                        "fedavg_cross_silo (the serving tier rides its "
                        "broadcast publishes); ignoring for %r", args.algo)
    if getattr(args, "wan_trace", None) \
            and args.algo != "fedavg_cross_silo":
        logging.warning("--wan_trace/--wan_profiles are only wired for "
                        "--algo fedavg_cross_silo (the WAN world drives "
                        "the actor protocol's liveness/admission paths); "
                        "ignoring for %r", args.algo)
    if (getattr(args, "prefetch_depth", 2) != 2
            and args.algo in _CUSTOM_LOOP_ALGOS):
        # the async round pipeline rides FedAvgAPI._host_round_inputs;
        # custom-loop algorithms pack serially (default depth stays
        # quiet — only an explicit request warrants the warning)
        logging.warning("--prefetch_depth is not wired for %r's custom "
                        "loop; ignoring %d", args.algo,
                        args.prefetch_depth)


def run_algo(args):
    ds, model, task = build_dataset_and_model(args)
    _validate_before_sink(args, ds)
    sink = MetricsSink(args.run_dir, config=vars(args),
                       use_wandb=args.use_wandb)
    tcfg = make_train_config(args)
    common = dict(comm_round=args.comm_round,
                  client_num_per_round=args.client_num_per_round,
                  frequency_of_the_test=args.frequency_of_the_test,
                  seed=args.seed, train=tcfg,
                  prefetch_depth=getattr(args, "prefetch_depth", 2))

    if args.algo == "fedavg":
        from fedml_tpu.experiments.main_fedavg import BACKEND_RUNNERS
        final = BACKEND_RUNNERS[args.backend](args, ds, model, task, sink)
        sink.finish()
        return final
    if args.algo == "fedavg_cross_silo":
        # the cross-silo actor protocol (server + one client manager per
        # silo over a comm backend), reference `mpirun -np k+1` topology
        # (distributed/fedavg/FedAvgAPI.py:20-67). Every silo
        # participates each round — the reference cross-silo CIFAR10
        # anchor config (benchmark/README.md:105: 10 silos, LDA
        # alpha=0.5, E=20, B=64, ResNet-56).
        from fedml_tpu.algorithms.fedavg_cross_silo import (
            run_fedavg_cross_silo)
        sink_live = [True]
        if args.frequency_of_the_test != 1:
            logging.warning("--frequency_of_the_test is not wired for "
                            "--algo fedavg_cross_silo (the actor protocol "
                            "evaluates every round); ignoring %d",
                            args.frequency_of_the_test)
        _, history = run_fedavg_cross_silo(
            ds, model, task=task,
            worker_num=args.client_num_per_round,
            comm_round=args.comm_round, train_cfg=tcfg, seed=args.seed,
            checkpoint_dir=args.checkpoint_dir or None,
            resume=args.resume,
            compress=getattr(args, "compress", False),
            compression=getattr(args, "compression", None),
            prefetch_depth=getattr(args, "prefetch_depth", 2),
            # fault tolerance: deadline-evicted stragglers + silo rejoin
            # + the seeded chaos harness (README "Fault tolerance")
            round_deadline_s=getattr(args, "round_deadline_s", None),
            min_quorum_frac=getattr(args, "min_quorum_frac", 0.5),
            heartbeat_s=getattr(args, "heartbeat_s", 0.0),
            fault_plan=getattr(args, "fault_plan", None),
            # elastic control plane: server failover + pace steering +
            # JOIN admission (README "Elastic control plane")
            server_checkpoint_dir=getattr(args, "server_checkpoint_dir",
                                          None),
            checkpoint_sync=getattr(args, "checkpoint_sync", False),
            pace_steering=getattr(args, "pace_steering", False),
            join_rate_limit=getattr(args, "join_rate_limit", 0.0),
            max_deadline_extensions=resolve_max_extensions(args),
            # federated serving tier (fedml_tpu/serve): hot-swapped
            # inference endpoint riding the round-close publishes
            serve_port=getattr(args, "serve_port", None),
            serve_staleness_rounds=getattr(args, "serve_staleness_rounds",
                                           2),
            # WAN world model (fedml_tpu/wan): diurnal churn +
            # heterogeneous stragglers driving the liveness/admission/
            # steering machinery (README "WAN-realistic federation")
            wan_trace=getattr(args, "wan_trace", None),
            wan_profiles=getattr(args, "wan_profiles", None),
            wan_round_s=getattr(args, "wan_round_s", 60.0),
            # flight recorder (fedml_tpu/obs): previously only the
            # main_fedavg runners threaded these — the fed_launch
            # cross-silo path silently dropped --obs_dir/--job_id
            obs_dir=getattr(args, "obs_dir", None),
            job_id=getattr(args, "job_id", None),
            # scale the join budget with the local work — on a 1-core
            # host the silo threads SERIALIZE, so the budget grows with
            # epochs x rounds x silos; the 1200 floor absorbs a
            # multi-minute XLA:CPU compile. This is an upper bound, not a
            # wait: fast hosts finish and join immediately.
            join_timeout_s=max(1200.0, 30.0 * args.epochs
                               * args.comm_round
                               * max(1, args.client_num_per_round)),
            # stream each round into metrics.jsonl as it lands: a long
            # chip protocol must be observable mid-run (a buffered-to-end
            # history is indistinguishable from a hang). The liveness
            # gate closes the hook before sink.finish(): on the
            # non-raising join-timeout path the daemon server thread can
            # complete further rounds AFTER this function returns, and
            # those must not write to a finished sink.
            round_record_hook=lambda rec: (
                sink_live[0] and sink.log(rec, step=rec.get("round"))))
        sink_live[0] = False
        sink.finish()
        return history[-1] if history else {}
    if args.checkpoint_dir:
        logging.warning("--checkpoint_dir is only wired for --algo fedavg "
                        "and fedavg_cross_silo; ignoring for %r", args.algo)
    if args.algo == "fedopt":
        from fedml_tpu.algorithms.fedopt import FedOptAPI, FedOptConfig
        api = FedOptAPI(ds, model, task=task, config=FedOptConfig(
            server_optimizer=args.server_optimizer,
            server_lr=args.server_lr,
            server_momentum=args.server_momentum, **common))
    elif args.algo == "fednova":
        from fedml_tpu.algorithms.fednova import FedNovaAPI, FedNovaConfig
        api = FedNovaAPI(ds, model, task=task, config=FedNovaConfig(
            gmf=args.gmf, mu=args.prox_mu, **common))
    elif args.algo == "fedavg_robust":
        from fedml_tpu.algorithms.fedavg_robust import (FedAvgRobustAPI,
                                                        FedAvgRobustConfig)
        edge_test = None
        if args.poison_pkl:
            from fedml_tpu.data.poisoned import (load_edge_case_artifact,
                                                 mix_edge_case_into_client)
            x_edge, y_edge = load_edge_case_artifact(
                args.poison_pkl, target_label=args.target_label)
            ds = mix_edge_case_into_client(
                ds, args.attacker_client, x_edge, y_edge,
                num_edge=args.poison_num_edge,
                num_clean=args.poison_num_clean, seed=args.seed)
            if args.poison_test_pkl:
                edge_test = load_edge_case_artifact(
                    args.poison_test_pkl, target_label=args.target_label)
        api = FedAvgRobustAPI(ds, model, task=task,
                              config=FedAvgRobustConfig(
                                  defense_type=args.defense_type,
                                  norm_bound=args.norm_bound,
                                  stddev=args.stddev,
                                  trim_ratio=args.trim_ratio,
                                  num_byzantine=args.num_byzantine,
                                  multi_m=args.multi_m,
                                  **common))
        if edge_test is not None:
            import jax.numpy as jnp

            from fedml_tpu.algorithms.fedavg import _normalized
            final = api.train()
            for rec in api.history:
                sink.log(rec, step=rec.get("round"))
            xh, yh = edge_test
            asr = _normalized(api._eval_fn(
                api.variables, jnp.asarray(xh), jnp.asarray(yh),
                jnp.ones(len(xh), jnp.float32)), "backdoor")
            final = {**final, "backdoor_asr": asr["backdoor_acc"]}
            sink.log({"backdoor_asr": final["backdoor_asr"]})
            sink.finish()
            logging.info("backdoor ASR on edge test set: %.4f",
                         final["backdoor_asr"])
            return final
    elif args.algo == "hierarchical":
        from fedml_tpu.algorithms.hierarchical import (HierarchicalConfig,
                                                       HierarchicalFedAvgAPI)
        api = HierarchicalFedAvgAPI(ds, model, task=task,
                                    config=HierarchicalConfig(
                                        global_comm_round=args.comm_round,
                                        group_comm_round=args.group_comm_round,
                                        group_num=args.group_num,
                                        client_num_per_round=(
                                            args.client_num_per_round),
                                        frequency_of_the_test=(
                                            args.frequency_of_the_test),
                                        seed=args.seed, train=tcfg))
    elif args.algo == "turboaggregate":
        from fedml_tpu.algorithms.fedavg import FedAvgConfig
        from fedml_tpu.algorithms.turboaggregate import (SecureFedAvgAPI,
                                                         TurboAggregateConfig)
        api = SecureFedAvgAPI(ds, model, task=task,
                              config=FedAvgConfig(**common),
                              secure_config=TurboAggregateConfig(
                                  frac_bits=args.frac_bits, seed=args.seed))
    elif args.algo == "decentralized":
        import numpy as np
        from fedml_tpu.algorithms.decentralized import (
            DecentralizedConfig, DecentralizedOnlineAPI)
        # carve the global stream into one sample stream per client and
        # binarize labels — the online API is the reference's SUSY-style
        # binary LR (decentralized_fl_api.py), not a multi-class trainer
        xg, yg = ds.train_data_global
        n = args.client_num_in_total
        T = len(xg) // n
        if T < args.comm_round:
            raise SystemExit(
                f"--algo decentralized streams --comm_round={args.comm_round} "
                f"samples per client, but {args.dataset!r} only provides "
                f"{T} per client at --client_num_in_total={n}; lower "
                f"--comm_round or --client_num_in_total")
        x = np.asarray(xg, np.float32).reshape(len(xg), -1)[:n * T]
        x = x.reshape(n, T, -1)
        y = (np.asarray(yg).reshape(-1)[:n * T] % 2).astype(
            np.float32).reshape(n, T)
        api = DecentralizedOnlineAPI(x, y, DecentralizedConfig(
            mode=args.mode, iteration_number=args.comm_round,
            learning_rate=args.lr, weight_decay=args.wd,
            topology_neighbors_num_undirected=(
                args.topology_neighbors_num_undirected),
            seed=args.seed))
        rec = {"regret": api.train(),
               "consensus_distance": api.consensus_distance()}
        sink.log(rec)
        sink.finish()
        logging.info("final: %s", rec)
        return rec
    elif args.algo == "fednas":
        from fedml_tpu.algorithms.fednas import FedNASAPI, FedNASConfig
        from fedml_tpu.models.darts import DartsNetwork
        if ds.train_data_global[0].ndim != 4:
            raise SystemExit(
                "fednas needs an NHWC image dataset (e.g. --dataset cifar10)")
        api = FedNASAPI(ds, DartsNetwork(C=8, num_classes=ds.class_num,
                                         layers=2),
                        FedNASConfig(comm_round=args.comm_round,
                                     epochs=args.epochs,
                                     batch_size=args.batch_size, lr=args.lr,
                                     arch_lr=args.arch_lr, seed=args.seed,
                                     variant=args.nas_variant,
                                     arch_unrolled=args.arch_unrolled))
        # FedNASAPI has no train() wrapper: drive the search rounds here
        for r in range(args.comm_round):
            rec = api.run_round(r)
            sink.log({k: v for k, v in rec.items() if k != "genotype"},
                     step=r)
            logging.info("round %d: search_loss=%.4f", r, rec["search_loss"])
        final = {**api.evaluate(), "genotype": str(api.history[-1]["genotype"])}
        if args.nas_retrain_rounds > 0:
            # the second half of the NAS workflow (reference model.py /
            # train.py): freeze the searched genotype into a fixed
            # evaluation network and train it federated from scratch
            from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
            from fedml_tpu.models.darts_eval import GenotypeNetwork

            eval_net = GenotypeNetwork(
                genotype=api.genotype(), C=8, num_classes=ds.class_num,
                layers=3, stem_multiplier=1)
            retrain = FedAvgAPI(
                ds, eval_net,
                config=FedAvgConfig(
                    comm_round=args.nas_retrain_rounds,
                    client_num_per_round=args.client_num_per_round,
                    frequency_of_the_test=args.frequency_of_the_test,
                    seed=args.seed, train=tcfg))
            retrain_final = retrain.train()
            for rec in retrain.history:
                sink.log({f"retrain_{k}": v for k, v in rec.items()},
                         step=rec.get("round"))
            final.update({f"retrain_{k}": v
                          for k, v in retrain_final.items()})
        sink.log({k: v for k, v in final.items() if k != "genotype"})
        sink.finish()
        logging.info("final: %s", final)
        return final
    elif args.algo == "centralized":
        from fedml_tpu.algorithms.centralized import CentralizedTrainer
        trainer = CentralizedTrainer(ds, model, task=task, cfg=tcfg,
                                     seed=args.seed)
        for _ in range(args.comm_round):
            trainer.train()
        rec = trainer.evaluate()
        sink.log(rec)
        sink.finish()
        return rec
    elif args.algo == "fedseg":
        from fedml_tpu.algorithms.fedavg import FedAvgConfig
        from fedml_tpu.algorithms.fedseg import FedSegAPI
        if ds.train_data_global[1].ndim != 3:
            raise SystemExit(
                "fedseg needs per-pixel labels [N, H, W] (e.g. --dataset "
                f"seg_shapes); {args.dataset!r} labels have shape "
                f"{ds.train_data_global[1].shape[1:]}")
        api = FedSegAPI(ds, model, config=FedAvgConfig(**common),
                        loss_mode=args.seg_loss)
    elif args.algo == "fedgkt":
        from fedml_tpu.algorithms.fedgkt import FedGKTAPI, FedGKTConfig
        from fedml_tpu.models.resnet_gkt import resnet8_56, resnet56_server
        if ds.train_data_global[0].ndim != 4:
            raise SystemExit(
                "fedgkt requires an NHWC image dataset (e.g. --dataset "
                f"cifar10); {args.dataset!r} samples have shape "
                f"{ds.train_data_global[0].shape[1:]}")
        api = FedGKTAPI(ds, resnet8_56(ds.class_num),
                        resnet56_server(ds.class_num),
                        FedGKTConfig(comm_round=args.comm_round,
                                     epochs_client=args.epochs_client,
                                     epochs_server=args.epochs_server,
                                     batch_size=args.batch_size,
                                     alpha=args.alpha,
                                     temperature=args.temperature,
                                     seed=args.seed,
                                     pretrained_client_path=(
                                         args.pretrained_path)))
    elif args.algo == "split_nn":
        from fedml_tpu.algorithms.split_nn import SplitNNAPI, SplitNNConfig
        from fedml_tpu.models.vfl import VFLDenseModel, VFLFeatureExtractor
        bottom = VFLFeatureExtractor(hidden_dims=(64, 32))
        top = VFLDenseModel(output_dim=ds.class_num, use_bias=True)
        api = SplitNNAPI(ds, bottom, top,
                         cut_input_shape=(bottom.hidden_dims[-1],),
                         config=SplitNNConfig(
                             epochs_per_node=args.epochs,
                             batch_size=args.batch_size,
                             lr=args.lr, wd=args.wd, seed=args.seed))
        for r in range(args.comm_round):
            rec = api.train_one_rotation(r)
            sink.log(rec, step=r)
        sink.finish()
        final = api.history[-1]
        logging.info("final: %s", final)
        return final
    elif args.algo == "vertical_fl":
        import numpy as np
        from fedml_tpu.algorithms.vertical_fl import VFLConfig, build_vfl
        xg, yg = ds.train_data_global
        xt, yt = ds.test_data_global
        x_train = np.asarray(xg, np.float32)
        x_test = np.asarray(xt, np.float32)
        # guest holds the labels (binarized: the reference VFL task is
        # binary logistic regression, party_models.py) and the first
        # feature block; hosts hold the rest
        y_train = (np.asarray(yg).reshape(-1) % 2).astype(np.float32)
        y_test = (np.asarray(yt).reshape(-1) % 2).astype(np.float32)
        cuts = np.array_split(np.arange(x_train.shape[1]), args.party_num)
        fixture = build_vfl([len(c) for c in cuts],
                            VFLConfig(epochs=args.comm_round,
                                      batch_size=args.batch_size,
                                      lr=args.lr, seed=args.seed))
        final = fixture.fit([x_train[:, c] for c in cuts], y_train,
                            [x_test[:, c] for c in cuts], y_test)
        for rec in fixture.history:
            sink.log(rec, step=rec["epoch"])
        sink.finish()
        logging.info("final: %s", final)
        return final
    elif args.algo == "fedavg_async":
        import numpy as np
        from fedml_tpu.algorithms.fedavg_async import run_fedavg_async
        _, history, server = run_fedavg_async(
            ds, model, task=task,
            worker_num=args.client_num_per_round, mode=args.async_mode,
            comm_round=args.comm_round, quorum=args.quorum,
            round_deadline_s=(args.round_deadline_s
                              if args.round_deadline_s is not None
                              else 10.0),
            alpha=args.async_alpha, poly_a=args.async_poly_a,
            max_updates=args.max_updates, train_cfg=tcfg, seed=args.seed,
            # fedasync mode warns and forces full precision inside
            compression=getattr(args, "compression", None),
            heartbeat_s=getattr(args, "heartbeat_s", 0.0),
            fault_plan=getattr(args, "fault_plan", None),
            # control plane (quorum mode only; fedasync warns + ignores)
            server_checkpoint_dir=getattr(args, "server_checkpoint_dir",
                                          None),
            checkpoint_sync=getattr(args, "checkpoint_sync", False),
            pace_steering=getattr(args, "pace_steering", False),
            join_rate_limit=getattr(args, "join_rate_limit", 0.0),
            max_deadline_extensions=resolve_max_extensions(args))
        for rec in history:
            sink.log(rec, step=rec["round"])
        final = dict(history[-1]) if history else {}
        if args.async_mode == "quorum":
            final["partial_rounds"] = list(server.partial_rounds)
        else:
            final["updates"] = len(server.update_log)
            final["mean_staleness"] = (
                float(np.mean([u["staleness"]
                               for u in server.update_log]))
                if server.update_log else 0.0)
        sink.log({k: v for k, v in final.items()
                  if not isinstance(v, list)})
        sink.finish()
        logging.info("final: %s", final)
        return final
    elif args.algo == "contribution":
        # the reference's contribution workflow driver
        # (main_fedavg_contribution.py:366-380): train the base federation,
        # then one leave-one-out retrain per client; report each client's
        # influence (mean |prob diff| on the test set) through the sink
        from fedml_tpu.algorithms.fedavg import FedAvgConfig
        from fedml_tpu.contribution.loo import LeaveOneOutMeasure
        measure = LeaveOneOutMeasure(ds, lambda: model,
                                     config=FedAvgConfig(**common),
                                     task=task)
        influence = measure.compute_influence()
        ranked = measure.ranked()
        for k, v in enumerate(influence):
            sink.log({"client": k, "influence": v}, step=k)
        final = {"influence": influence, "ranked": ranked}
        sink.log({f"influence_client_{k}": v
                  for k, v in enumerate(influence)})
        sink.finish()
        logging.info("final: %s", final)
        return final
    else:  # pragma: no cover - argparse choices rejects unknown algos
        raise SystemExit(f"--algo {args.algo} is not wired in fed_launch")

    return _log_history(api, sink,
                        fused_rounds=getattr(args, "fused_rounds", 0))


def main(argv=None):
    from fedml_tpu.experiments.main_fedavg import apply_ci_truncation
    from fedml_tpu.utils import enable_persistent_compilation_cache

    parser = argparse.ArgumentParser("fedml_tpu fed_launch")
    parser.add_argument("--algo", type=str, default="fedavg", choices=ALGOS)
    add_federated_args(parser)
    add_algo_args(parser)
    args = apply_ci_truncation(parser.parse_args(argv))
    enable_persistent_compilation_cache()
    logging.basicConfig(level=logging.INFO)
    from fedml_tpu.utils.tracing import profile
    with profile(getattr(args, "profile_dir", None)):
        return run_algo(args)


if __name__ == "__main__":
    main()
