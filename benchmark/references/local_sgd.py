"""Plain reference of one FedAvg round of local SGD (classification).

Each client starts from the global parameters and takes ``epochs`` passes
of minibatch SGD over its own rows; the new global parameters are the mean
of the clients' results weighted by their real row counts (McMahan et al.,
arXiv:1602.05629, Algorithm 1). Straightforward ``jax.numpy`` in float32
under ``default_matmul_precision("highest")``: a Python loop over clients
and batches, one jitted one-batch step, a numpy weighted mean in float64.
No vmap, packer, kernel or driver code.

What it takes from the program, and why (each a departure from a fully
independent reference, listed in PERF.md):

* the *data order*: ``core.sampling.round_keys`` and
  ``trainer.functional.make_batch_schedule`` say which rows meet in which
  step and which dropout key a step gets. They do not say how a step is
  computed, and two runs can only be compared step for step on one order;
* the *forward pass*: the zoo module's ``apply`` (plain Flax, no kernels).
  Dropout masks come from Flax's own key folding, which a hand-written
  forward pass could not reproduce.

Its own: the masked mean cross-entropy, the gradient step, the skipping of
batches that hold padding only, and the aggregation.
"""

from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _only_params(variables) -> None:
    extra = sorted(k for k in variables if k != "params")
    if extra:
        raise ValueError(
            "the local_sgd reference handles models whose variables are "
            f"parameters only; this one also has {extra}")


def masked_cross_entropy(logits, labels, mask):
    """(sum of the real rows' cross-entropies, number of real rows)."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    per_row = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(per_row * mask), jnp.sum(mask)


def make_step(module, task: str, train: Dict):
    """One SGD step on one batch: ``(params, x, y, mask, key) ->
    (params, loss_sum, count)``. The loss is the mean over the batch's real
    rows; ``key`` is the step's dropout key."""
    if task != "classification":
        raise ValueError(f"the local_sgd reference has no {task!r} loss")
    if train.get("client_optimizer", "sgd") != "sgd":
        raise ValueError("the local_sgd reference is plain SGD")
    lr = float(train["lr"])

    def step(params, x, y, mask, key):
        def loss_fn(p):
            logits = module.apply({"params": p}, x, train=True,
                                  rngs={"dropout": key})
            loss_sum, count = masked_cross_entropy(logits, y, mask)
            return loss_sum / jnp.maximum(count, 1.0), (loss_sum, count)

        grads, (loss_sum, count) = jax.grad(loss_fn, has_aux=True)(params)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss_sum, count

    return step


def flops_per_row(module, task: str, train: Dict, variables, sample_x,
                  count_flops) -> float:
    """Matrix-multiply and convolution FLOPs that one training row needs,
    forward and backward: the count of this reference's own step on one
    batch, divided by the batch. Nothing of the program is traced, so
    recomputation or padding in the program cannot move it."""
    _only_params(variables)
    bsz = int(train["batch_size"])
    x = jnp.zeros((bsz,) + tuple(sample_x.shape[1:]), jnp.float32)
    y = jnp.zeros((bsz,), jnp.int32)
    mask = jnp.ones((bsz,), jnp.float32)
    step = make_step(module, task, train)
    return count_flops(step, variables["params"], x, y, mask,
                       jax.random.key(0)) / bsz


def run_round(module, task: str, train: Dict, variables, dataset, *,
              seed: int, round_idx: int, clients: Sequence[int],
              aggregate: bool) -> Dict:
    """Local SGD from ``variables`` for every client of ``clients`` in round
    ``round_idx``. Returns ``{"loss_sum": {client: float}, "count": {client:
    float}, "variables": tree}``, the last the mean of the clients' results
    weighted by their rows (None unless ``aggregate``). The mean is folded
    in client by client, so the host holds one client's model at a time."""
    from fedml_tpu.core.sampling import round_keys
    from fedml_tpu.trainer.functional import make_batch_schedule

    _only_params(variables)
    bsz, epochs = int(train["batch_size"]), int(train["epochs"])
    clients = [int(c) for c in clients]
    sizes = [len(dataset.train_data_local_dict[c][0]) for c in clients]
    total = float(sum(sizes))
    # one padded length for the whole federation, so the schedule below has
    # one shape whatever clients are asked for; the order of the real rows
    # does not depend on it
    n_pad = -(-max(dataset.train_data_local_num_dict.values()) // bsz) * bsz
    masks = (np.arange(n_pad)[None, :]
             < np.asarray(sizes)[:, None]).astype(np.float32)

    with jax.default_matmul_precision("highest"):
        plain_step = make_step(module, task, train)
        # keys travel as their raw words, so a step costs no device indexing
        step = jax.jit(lambda p, x, y, m, key_words: plain_step(
            p, x, y, m, jax.random.wrap_key_data(key_words)))
        _, keys, _ = round_keys(jax.random.key(seed), round_idx,
                                jnp.asarray(clients, dtype=jnp.uint32))
        # the program's order: [C, steps, bsz] row indices (padding rows
        # sorted last) and one dropout key per step
        batch_idx, step_keys = jax.jit(jax.vmap(
            lambda k, m: make_batch_schedule(n_pad, epochs, bsz, True, k,
                                             mask=m)))(keys,
                                                       jnp.asarray(masks))
        batch_idx = np.asarray(batch_idx)
        step_keys = np.asarray(jax.random.key_data(step_keys))
        init = jax.device_put(variables["params"])
        steps, mean = [], None
        for i, (cid, n) in enumerate(zip(clients, sizes)):
            x, y = dataset.train_data_local_dict[cid]
            params, mine = init, []
            for b in range(batch_idx.shape[1]):
                real = batch_idx[i, b] < n
                if not real.any():
                    continue  # a batch of padding only is not a step
                rows = np.where(real, batch_idx[i, b], 0)
                shape = (bsz,) + (1,) * (x.ndim - 1)
                params, loss_sum, count = step(
                    params, x[rows] * real.reshape(shape),
                    np.where(real, y[rows], 0), real.astype(np.float32),
                    step_keys[i, b])
                mine.append((loss_sum, count))
            steps.append(mine)
            if aggregate:
                part = jax.tree.map(
                    lambda p: np.asarray(p, np.float64) * (n / total),
                    jax.device_get(params))
                mean = part if mean is None else jax.tree.map(
                    np.ndarray.__iadd__, mean, part)
        # [client][step] (loss_sum, count), read once at the end
        sums = [np.sum(np.asarray(mine, np.float64).reshape(-1, 2), axis=0)
                for mine in jax.device_get(steps)]

    return {
        "loss_sum": {c: float(v[0]) for c, v in zip(clients, sums)},
        "count": {c: float(v[1]) for c, v in zip(clients, sums)},
        "variables": (None if mean is None else {"params": jax.tree.map(
            lambda p: p.astype(np.float32), mean)}),
    }
