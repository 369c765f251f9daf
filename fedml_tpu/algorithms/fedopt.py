"""FedOpt — adaptive server optimization (FedAdam / FedAdagrad / FedYogi...).

Reference semantics (fedml_api/distributed/fedopt/FedOptAggregator.py:70-123
and standalone/fedopt/fedopt_api.py): do the FedAvg sample-weighted average,
form the pseudo-gradient ``w_old - w_avg``, and hand it to a persistent
server-side optimizer; non-parameter state (BN buffers) takes the plain
average. The reference reflects over ``torch.optim.Optimizer.__subclasses__``
(optrepo.py:7) to resolve ``--server_optimizer`` by name; we mirror that with
an optax registry. Everything — local training, aggregation, pseudo-grad,
server update — runs inside the one jitted round program.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.core import pytree as pt
from fedml_tpu.algorithms.fedavg import (FedAvgAPI, FedAvgConfig,
                                         FusedRounds)
from fedml_tpu.data.base import FederatedDataset
from fedml_tpu.trainer.functional import round_lr_scale

#: name -> constructor(lr, **kw); parity with OptRepo's name2cls lookup
OPTIMIZER_REPO = {
    "sgd": lambda lr, momentum=0.0, **kw: optax.sgd(lr, momentum=momentum or None),
    "adam": lambda lr, **kw: optax.adam(lr, **kw),
    "adamw": lambda lr, **kw: optax.adamw(lr, **kw),
    "adagrad": lambda lr, **kw: optax.adagrad(lr, **kw),
    "yogi": lambda lr, **kw: optax.yogi(lr, **kw),
    "rmsprop": lambda lr, **kw: optax.rmsprop(lr, **kw),
    "lamb": lambda lr, **kw: optax.lamb(lr, **kw),
}


def get_server_optimizer(name: str, lr: float, **kw) -> optax.GradientTransformation:
    try:
        return OPTIMIZER_REPO[name.lower()](lr, **kw)
    except KeyError:
        raise ValueError(
            f"unknown server_optimizer {name!r}; have {sorted(OPTIMIZER_REPO)}")


@dataclasses.dataclass(frozen=True)
class FedOptConfig(FedAvgConfig):
    """Adds the reference's --server_optimizer / --server_lr flags
    (main_fedopt.py:54-60)."""

    server_optimizer: str = "adam"
    server_lr: float = 1e-3
    server_momentum: float = 0.0


class FedOptAPI(FedAvgAPI):
    """FedAvg outer loop with a persistent server optimizer on the
    pseudo-gradient. ``config`` must be a FedOptConfig."""

    def __init__(self, dataset: FederatedDataset, module,
                 task: str = "classification",
                 config: Optional[FedOptConfig] = None,
                 delete_client: Optional[int] = None):
        config = config or FedOptConfig()
        super().__init__(dataset, module, task, config,
                         delete_client=delete_client)
        kw = {}
        if config.server_optimizer == "sgd" and config.server_momentum:
            kw["momentum"] = config.server_momentum
        self._server_tx = get_server_optimizer(config.server_optimizer,
                                               config.server_lr, **kw)
        self.server_opt_state = self._server_tx.init(self.variables["params"])

        body = self._vmapped_body
        server_tx = self._server_tx

        def round_fn(variables, opt_state, x, y, mask, keys, weights,
                     round_idx):
            stacked, totals = body(variables, x, y, mask, keys,
                                   round_lr_scale(self.config.train,
                                                  round_idx))
            avg = pt.tree_weighted_mean(stacked, weights)
            # pseudo-gradient: w_old - w_avg (the server walks opposite the
            # aggregate displacement; FedOptAggregator.py:109-123)
            pseudo_grad = pt.tree_sub(variables["params"], avg["params"])
            updates, opt_state = server_tx.update(pseudo_grad, opt_state,
                                                  variables["params"])
            new_params = optax.apply_updates(variables["params"], updates)
            # non-param collections (BN stats) keep the plain average
            new_vars = {**avg, "params": new_params}
            return new_vars, opt_state, totals

        # donate the dead global model + opt state buffers (HBM reuse)
        self._fedopt_round_fn = jax.jit(round_fn, donate_argnums=(0, 1))
        # unjitted body, shared with FedOptFusedRounds (one source of truth)
        self._fedopt_round_fn_py = round_fn

    def run_round(self, round_idx: int):
        idxs, (x, y, mask, keys, weights, _) = self._host_round_inputs(
            round_idx)
        self.variables, self.server_opt_state, stats = self._fedopt_round_fn(
            self.variables, self.server_opt_state, x, y, mask, keys, weights,
            jnp.uint32(round_idx))
        return idxs, stats


class FedOptFusedRounds(FusedRounds):
    """FusedRounds for FedOpt: the scan carry is (variables,
    server_opt_state), so the persistent server optimizer (Adam/Yogi/...)
    advances INSIDE the R-round scan — the whole adaptive-server outer
    loop becomes one device program. Same RNG chain as the host loop;
    FedOpt's aggregation ignores agg_key just like FedOptAPI.run_round."""

    def _init_carry(self):
        return (self.api.variables, self.api.server_opt_state)

    def _store_carry(self, carry) -> None:
        self.api.variables, self.api.server_opt_state = carry

    def _round(self, carry, x, y, mask, keys, weights, agg_key, r):
        variables, opt_state = carry
        new_vars, new_opt, totals = self.api._fedopt_round_fn_py(
            variables, opt_state, x, y, mask, keys, weights, r)
        return (new_vars, new_opt), totals


FedOptAPI._fused_driver_cls = FedOptFusedRounds


# -- static-analysis hook (fedml_tpu.analysis layer 2) ----------------------
from fedml_tpu.analysis.registry import AuditSpec, hot_entry_point  # noqa: E402


@hot_entry_point("fedopt.round_fn")
def _audit_fedopt_round() -> AuditSpec:
    """FedOpt's server-optimizer round (adam server tx) over three real
    rounds' host inputs — the carry includes opt_state, so a signature
    drift in EITHER the model or the optimizer tree forks the cache."""
    import jax.numpy as jnp

    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig

    ds = make_blob_federated(client_num=4, n_samples=200, seed=0)
    api = FedOptAPI(
        ds, LogisticRegression(num_classes=ds.class_num),
        config=FedOptConfig(
            comm_round=3, client_num_per_round=2, pack="global",
            prefetch_depth=0, server_optimizer="adam", server_lr=0.01,
            train=TrainConfig(epochs=1, batch_size=8)))

    def inputs(r):
        _, _, (x, y, mask, keys, w, _) = api._pack_round(r)
        return (api.variables, api.server_opt_state, x, y, mask, keys, w,
                jnp.uint32(r))

    return AuditSpec(fn=api._fedopt_round_fn,
                     sweep=[inputs(r) for r in range(3)],
                     max_lowerings=1, grad_path=True)
