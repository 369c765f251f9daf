"""The ``simulation`` driver: ``FedAvgAPI``, the whole cohort under one
``vmap`` on one device, with the settings of the program's launcher
(``experiments/flagship_scale.run_driver``): cohort-bucket packing,
prefetch depth 2, a 2000-row training subsample and the full test union at
each evaluation, float32, default matmul precision."""


def build(dataset, module, task, *, train, cohort, eval_every, rounds, seed,
          devices):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.trainer.functional import TrainConfig

    del devices  # one vmap on the default device
    return FedAvgAPI(dataset, module, task=task, config=FedAvgConfig(
        comm_round=rounds, client_num_per_round=cohort,
        frequency_of_the_test=eval_every, seed=seed,
        eval_train_subsample=2000, eval_test_subsample=None,
        pack="cohort", prefetch_depth=2, train=TrainConfig(**train)))


def evaluate(api, round_idx):
    """What ``FedAvgAPI.train`` calls at a test round; it returns host
    floats, so it has finished when it returns."""
    return api.evaluate(round_idx)
