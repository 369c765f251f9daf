"""The ``spmd`` driver: ``DistributedFedAvgAPI``, the cohort sharded over a
one-axis mesh of the cell's chips, the FedAvg mean a ``psum``, with the
settings of the program's launcher (``experiments/flagship_scale
.run_driver``): cohort-bucket packing, prefetch depth 2, the full test union
at each evaluation, float32, default matmul precision."""


def build(dataset, module, task, *, train, cohort, eval_every, rounds, seed,
          devices):
    from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                         DistributedFedAvgConfig, build_mesh)
    from fedml_tpu.trainer.functional import TrainConfig

    return DistributedFedAvgAPI(
        dataset, module, task=task,
        mesh=build_mesh({"clients": len(devices)}, list(devices)),
        config=DistributedFedAvgConfig(
            comm_round=rounds, client_num_per_round=cohort,
            frequency_of_the_test=eval_every, seed=seed, pack="cohort",
            eval_test_subsample=None, prefetch_depth=2,
            train=TrainConfig(**train)))


def evaluate(api, round_idx):
    """What ``DistributedFedAvgAPI.train`` does at a test round: the
    sharded evaluation, normalised to host floats (which waits for it)."""
    stats = api._eval_global()
    total = max(1.0, float(stats["count"]))
    return {"round": round_idx,
            "test_acc": float(stats["correct_sum"]) / total,
            "test_loss": float(stats["loss_sum"]) / total,
            "test_total": float(stats["count"])}
