"""Plain reference of one FedAvg round of local SGD on the SambaY hybrid
decoder (Phi-4-mini-flash-reasoning, arXiv:2507.06607): the architecture's
own forward pass, its loss, gradient and SGD step, and the weighted mean.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: the state-space recurrence step by
step (``lax.scan`` over positions), attention as whole ``[T, T]`` score
matrices with explicit masks, the logits of a whole row at once; a Python
loop over clients and batches, one jitted one-batch step, a numpy weighted
mean in float64. No chunking, no kernel, no ``module.apply``, no vmap, packer
or driver code.

The layer equations (``s = LN(x)``, ``x += Mix(s)``, then ``x += W_down(
silu(g) * p)`` with ``[g, p] = W_gate_up LN'(x)``; published layer ``l`` of
``L``, here 32):

* ``l`` even, ``l <= L/2`` - Mamba-1 (arXiv:2312.00752): ``[a, z] = W_in s``;
  ``c = silu(causal depthwise conv_4(a) + b)``; ``[dt, B, C] = W_x c``;
  ``D = softplus(W_dt dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(D_t A)
  h_{t-1} + (D_t c_t) outer B_t``; ``y_t = h_t C_t + D_skip c_t``; ``Mix =
  W_out(y * silu(z))``. Layer ``L/2`` hands on ``y`` as the memory ``m``.
* ``l`` odd - differential attention (arXiv:2410.05258) over adjacent head
  pairs: ``o = softmax(q1 k1'/sqrt(d) + M) V - lam softmax(q2 k2'/sqrt(d) +
  M) V``, ``V = [v1; v2]``, ``lam = exp(lq1.lk1) - exp(lq2.lk2) + lam_init``,
  ``lam_init = 0.8 - 0.6 exp(-0.3 l)``; ``Mix = W_o concat((1 - lam_init)
  RMSNorm(o))``. ``l < L/2``: ``M`` also bars keys more than ``window - 1``
  back. ``l = L/2 + 1``: full causal, K and V kept. Later odd layers: only
  ``q`` is computed, K and V are layer ``L/2 + 1``'s.
* ``l`` even, ``l > L/2`` - gated memory unit: ``Mix = W_2(m * silu(W_1 s))``.

Final LayerNorm, logits ``x E'`` over the rows of the (tied) embedding held.

Departures from the published code that the builder knows of: layers are
rematerialised with ``jax.checkpoint`` in ``run_round`` so that the round
fits a chip beside the driver (the arithmetic is the same; ``flops_per_row``
counts the step without it); the pairing of query pairs with key/value pairs
(query pair ``j`` reads pair ``j // 2``, grouped-query attention over pairs)
and the Mamba sizes are the family's conventions, listed under ``assumed``
in the configuration file; dropout is 0 in the published config and absent
here.

What it takes from the program, and why:

* the *data order*: ``core.sampling.round_keys`` and
  ``trainer.functional.make_batch_schedule`` say which rows meet in which
  step. They do not say how a step is computed, and two runs can only be
  compared step for step on one order;
* the *names* of the parameter tree's leaves and the module's
  *hyperparameters* (widths, head counts, window, the published indices of
  the layers held), read as attributes: they say what the numbers in
  ``variables`` mean, not what to do with them.

Its own: every equation above, the row-mean cross-entropy, the gradient
step, the skipping of batches that hold padding only, and the aggregation.
"""

from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

TASK = "lm_rows"
#: elements one task of the host-side mean handles (numpy releases the GIL,
#: so the tasks of a leaf run on several cores)
_CHUNK = 1 << 22


def _only_params(variables) -> None:
    extra = sorted(k for k in variables if k != "params")
    if extra:
        raise ValueError(
            "the hybrid_lm_local_sgd reference handles models whose "
            f"variables are parameters only; this one also has {extra}")


def _norm(p, name, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p[f"{name}_scale"] \
        + p[f"{name}_bias"]


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _mamba(p, s, hp):
    inner, n = p["a_log"].shape
    rank = p["dt_proj"].shape[0]
    az = s @ p["in_proj"]
    a, z = az[:, :inner], az[:, inner:]
    taps, length = p["conv_kernel"].shape[0], s.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, inner), a.dtype), a])
    conv = p["conv_bias"] + sum(
        padded[j:j + length] * p["conv_kernel"][j] for j in range(taps))
    c = _silu(conv)
    dbc = c @ p["x_proj"]
    delta = _softplus(dbc[:, :rank] @ p["dt_proj"] + p["dt_bias"])
    b_t, c_t = dbc[:, rank:rank + n], dbc[:, rank + n:]
    a_mat = -jnp.exp(p["a_log"])

    def step(h, inp):
        d, u, b, cc = inp
        h = jnp.exp(d[:, None] * a_mat) * h + (d * u)[:, None] * b[None, :]
        return h, (h * cc[None, :]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros((inner, n), s.dtype),
                        (delta, c, b_t, c_t))
    y = y + p["d_skip"] * c
    return (y * _silu(z)) @ p["out_proj"], y


def _softmax_rows(scores, allowed):
    scores = jnp.where(allowed, scores, -jnp.inf)
    scores = scores - scores.max(-1, keepdims=True)
    e = jnp.exp(scores)
    return e / e.sum(-1, keepdims=True)


def _diff_attention(p, q, k, v, layer, window, hp):
    length, dim = q.shape[0], hp["head_dim"]
    q = q.reshape(length, hp["num_heads"] // 2, 2, dim)
    k = k.reshape(length, hp["num_kv_heads"] // 2, 2, dim)
    v = v.reshape(length, hp["num_kv_heads"] // 2, 2 * dim)
    group = q.shape[1] // k.shape[1]
    pos = jnp.arange(length)
    allowed = pos[None, :] <= pos[:, None]
    if window is not None:
        allowed = allowed & (pos[:, None] - pos[None, :] < window)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp((p["lambda_q1"] * p["lambda_k1"]).sum())
           - jnp.exp((p["lambda_q2"] * p["lambda_k2"]).sum()) + lam_init)
    outs = []
    for pair in range(q.shape[1]):
        kv = pair // group
        p1 = _softmax_rows(q[:, pair, 0] @ k[:, kv, 0].T / math.sqrt(dim),
                           allowed)
        p2 = _softmax_rows(q[:, pair, 1] @ k[:, kv, 1].T / math.sqrt(dim),
                           allowed)
        o = p1 @ v[:, kv] - lam * (p2 @ v[:, kv])
        o = o / jnp.sqrt((o ** 2).mean(-1, keepdims=True) + hp["eps"])
        outs.append((1.0 - lam_init) * o * p["subln_scale"])
    return jnp.concatenate(outs, axis=-1)


def _kind(layer: int, published: int) -> str:
    half = published // 2
    if layer % 2 == 0:
        return "mamba" if layer <= half else "gmu"
    if layer < half:
        return "window"
    return "full" if layer == half + 1 else "cross"


def _layer(p, x, memory, kv, layer, hp):
    kind = _kind(layer, hp["published"])
    s = _norm(p, "norm1", x, hp["eps"])
    if kind == "mamba":
        mix, y = _mamba(p, s, hp)
        if layer == hp["published"] // 2:
            memory = y
    elif kind == "gmu":
        mix = (memory * _silu(s @ p["gmu_in"])) @ p["gmu_out"]
    else:
        if kind == "cross":
            q = s @ p["q_proj"] + p["q_bias"]
            k, v = kv
        else:
            qkv = s @ p["qkv_proj"] + p["qkv_bias"]
            width = hp["num_heads"] * hp["head_dim"]
            kv_width = hp["num_kv_heads"] * hp["head_dim"]
            q, k, v = (qkv[:, :width], qkv[:, width:width + kv_width],
                       qkv[:, width + kv_width:])
            if kind == "full":
                kv = (k, v)
        o = _diff_attention(p, q, k, v, layer,
                            hp["window"] if kind == "window" else None, hp)
        mix = o @ p["o_proj"] + p["o_bias"]
    x = x + mix
    gu = _norm(p, "norm2", x, hp["eps"]) @ p["gate_up_proj"]
    half = gu.shape[-1] // 2
    return x + (_silu(gu[:, :half]) * gu[:, half:]) @ p["down_proj"], \
        memory, kv


def hyperparameters(module) -> Dict:
    """The module's sizes, read as attributes."""
    return {"num_heads": int(module.num_heads),
            "num_kv_heads": int(module.num_kv_heads),
            "head_dim": int(module.hidden_size) // int(module.num_heads),
            "window": int(module.sliding_window),
            "eps": float(module.layer_norm_eps),
            "layers": tuple(int(i) for i in module.layer_ids),
            "published": int(module.published_layers)}


def logits_of(params, hp, tokens, remat: bool = False):
    """``[T, V]`` logits of one sequence of token ids ``[T]``."""
    x, memory, kv = params["embedding"][tokens], None, None
    for layer in hp["layers"]:
        fn = (lambda p, x, m, kv, layer=layer:  # noqa: E731
              _layer(p, x, m, kv, layer, hp))
        if remat:
            fn = jax.checkpoint(fn)
        x, memory, kv = fn(params[f"layer_{layer:02d}"], x, memory, kv)
    x = _norm(params["final_norm"], "norm1", x, hp["eps"])
    return x @ params["embedding"].T


def row_mean_cross_entropy(logits, targets):
    """Mean over a row's positions of the cross-entropy; logits [T, V]."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].mean()


def make_step(module, task: str, train: Dict, remat: bool):
    """One SGD step on one batch of rows: ``(params, x, y, mask, key) ->
    (params, loss_sum, count)``. The loss is the mean over the batch's real
    rows of each row's mean cross-entropy; ``key`` is unused (no
    dropout)."""
    if task != TASK:
        raise ValueError(f"the hybrid_lm_local_sgd reference has no "
                         f"{task!r} loss")
    if train.get("client_optimizer", "sgd") != "sgd":
        raise ValueError("the hybrid_lm_local_sgd reference is plain SGD")
    lr = float(train["lr"])
    hp = hyperparameters(module)

    def step(params, x, y, mask, key):
        del key

        def loss_fn(p):
            rows = jnp.stack([
                row_mean_cross_entropy(logits_of(p, hp, x[i], remat), y[i])
                for i in range(x.shape[0])])
            loss_sum, count = jnp.sum(rows * mask), jnp.sum(mask)
            return loss_sum / jnp.maximum(count, 1.0), (loss_sum, count)

        grads, (loss_sum, count) = jax.grad(loss_fn, has_aux=True)(params)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss_sum, count

    return step


def _fold_in(mean, leaves, weight: float, pool) -> None:
    """``mean += weight * leaves`` in float64 on the host, one leaf at a time
    (so one leaf of one client is all that is held beside the mean; ``leaves``
    is emptied), a leaf's chunks spread over ``pool``'s threads. Element by element the arithmetic
    is numpy's float64 multiply and add."""

    def task(job):
        total, leaf, at = job
        total[at] += np.asarray(leaf[at], np.float64) * weight

    for total in mean:
        # popped, so that the device buffer and the host copy JAX caches on
        # it go as soon as the leaf is folded in
        flat = np.asarray(leaves.pop(0)).reshape(-1)  # device -> host
        list(pool.map(task, [
            (total.reshape(-1), flat, slice(i, i + _CHUNK))
            for i in range(0, flat.size, _CHUNK)]))


def _as_float32(leaves):
    """The float64 leaves as float32, each freed as it is converted."""
    out = []
    while leaves:
        out.append(leaves.pop(0).astype(np.float32))
    return out


def flops_per_row(module, task: str, train: Dict, variables, sample_x,
                  count_flops) -> float:
    """Matrix-multiply FLOPs that one training row (one packed sequence)
    needs, forward and backward: the count of this reference's own step on
    one batch, without rematerialisation, divided by the batch. Nothing of
    the program is traced, so recomputation in the program cannot move it.
    The recurrence is elementwise and counts nothing."""
    _only_params(variables)
    bsz = int(train["batch_size"])
    x = jnp.zeros((bsz,) + tuple(sample_x.shape[1:]), jnp.int32)
    mask = jnp.ones((bsz,), jnp.float32)
    step = make_step(module, task, train, remat=False)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          variables["params"])
    return count_flops(step, shapes, x, x, mask, jax.random.key(0)) / bsz


def run_round(module, task: str, train: Dict, variables, dataset, *,
              seed: int, round_idx: int, clients: Sequence[int],
              aggregate: bool) -> Dict:
    """Local SGD from ``variables`` for every client of ``clients`` in round
    ``round_idx``. Returns ``{"loss_sum": {client: float}, "count": {client:
    float}, "variables": tree}``, the last the mean of the clients' results
    weighted by their rows (None unless ``aggregate``). One client at a time
    on the device (its parameters are donated from step to step), the mean
    folded in on the host in float64, leaf by leaf."""
    from fedml_tpu.core.sampling import round_keys
    from fedml_tpu.trainer.functional import make_batch_schedule

    _only_params(variables)
    bsz, epochs = int(train["batch_size"]), int(train["epochs"])
    clients = [int(c) for c in clients]
    sizes = [len(dataset.train_data_local_dict[c][0]) for c in clients]
    total = float(sum(sizes))
    n_pad = -(-max(dataset.train_data_local_num_dict.values()) // bsz) * bsz
    masks = (np.arange(n_pad)[None, :]
             < np.asarray(sizes)[:, None]).astype(np.float32)

    with jax.default_matmul_precision("highest"), ThreadPoolExecutor(
            min(8, os.cpu_count() or 1)) as pool:
        plain_step = make_step(module, task, train, remat=True)
        step = jax.jit(lambda p, x, y, m: plain_step(p, x, y, m, None),
                       donate_argnums=(0,))
        _, keys, _ = round_keys(jax.random.key(seed), round_idx,
                                jnp.asarray(clients, dtype=jnp.uint32))
        batch_idx, _ = jax.jit(jax.vmap(
            lambda k, m: make_batch_schedule(n_pad, epochs, bsz, True, k,
                                             mask=m)))(keys,
                                                       jnp.asarray(masks))
        batch_idx = np.asarray(batch_idx)
        sums, mean = [], None
        for i, (cid, n) in enumerate(zip(clients, sizes)):
            x, y = dataset.train_data_local_dict[cid]
            params, mine = jax.device_put(variables["params"]), []
            for b in range(batch_idx.shape[1]):
                real = batch_idx[i, b] < n
                if not real.any():
                    continue  # a batch of padding only is not a step
                rows = np.where(real, batch_idx[i, b], 0)
                params, loss_sum, count = step(
                    params, x[rows] * real[:, None], y[rows] * real[:, None],
                    real.astype(np.float32))
                mine.append((loss_sum, count))
            sums.append(np.sum(np.asarray(jax.device_get(mine), np.float64)
                               .reshape(-1, 2), axis=0))
            leaves, treedef = jax.tree.flatten(params)
            del params
            if aggregate:
                if mean is None:
                    mean = [np.zeros(leaf.shape, np.float64)
                            for leaf in leaves]
                _fold_in(mean, leaves, n / total, pool)
            del leaves

    return {
        "loss_sum": {c: float(v[0]) for c, v in zip(clients, sums)},
        "count": {c: float(v[1]) for c, v in zip(clients, sums)},
        "variables": (None if mean is None else {
            "params": jax.tree.unflatten(treedef, _as_float32(mean))}),
    }
