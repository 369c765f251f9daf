"""The ``silo`` driver: ``FedAvgAPI`` with the cohort *folded* - the silos of
a round train one after another inside the one round program and each result
is folded into a running sum (``FedAvgConfig.fold_clients``) - for models a
cohort cannot hold a copy each of. Cohort-bucket packing and prefetch depth 2
as the ``sim`` driver has them; float32, default matmul precision. An
evaluation is a forward pass over the held-out union only (the program's own
``evaluate`` also sweeps a training subsample, which for a language model
doubles the tokens and tells the operator nothing the round's local loss does
not)."""


def build(dataset, module, task, *, train, cohort, eval_every, rounds, seed,
          devices):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.trainer.functional import TrainConfig

    del devices  # one program on the default device
    return FedAvgAPI(dataset, module, task=task, config=FedAvgConfig(
        comm_round=rounds, client_num_per_round=cohort,
        frequency_of_the_test=eval_every, seed=seed,
        eval_train_subsample=None, eval_test_subsample=None,
        pack="cohort", prefetch_depth=2, fold_clients=True,
        train=TrainConfig(**train)))


def evaluate(api, round_idx):
    """The held-out union through the program's evaluation program; it
    returns host floats, so it has finished when it returns."""
    _, test = api._eval_arrays()
    with api.timer.phase("eval"):
        stats = api._eval_fn(api.variables, *test)
        total = max(1.0, float(stats["count"]))
        return {"round": round_idx,
                "test_acc": float(stats["correct_sum"]) / total,
                "test_loss": float(stats["loss_sum"]) / total,
                "test_total": float(stats["count"])}
