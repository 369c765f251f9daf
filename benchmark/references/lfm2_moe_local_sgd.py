"""Plain reference of one FedAvg round of local SGD on the LFM2-MoE hybrid
decoder (LFM2-8B-A1B, ``model_type`` ``lfm2_moe``): the architecture's own
forward pass, its loss, gradient and SGD step, and the weighted mean.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: attention head by head as whole
``[T, T]`` score matrices with an explicit mask, the short convolution as
shifted products, the sparse block as a loop over the experts held, each
applied to *every* token and weighted by the token's routing weight (zero
where the expert was not chosen). The two loops - over the 32 query heads,
over the experts held - are ``lax.scan``s: one program for every head and
expert. No sort, no grouped product, no blocks, no
``module.apply``, nothing of ``fedml_tpu/ops``.

The layer equations (``s = RMSNorm(x)``, ``x += Op(s)``, ``x += FF(
RMSNorm'(x))``; ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale``;
published layer ``l``):

* ``layer_types[l] == "conv"`` - gated short convolution: ``[B, C, u] =
  W_in s``; ``v = B * u``; ``c_t = sum_{j < L} k_j * v_{t-j}`` per channel
  (zeros before the row); ``Op = W_out(C * c)``.
* ``"full_attention"`` - per query head ``h`` (key/value head ``h // (H /
  H_kv)``): ``q, k`` RMSNorm-ed over the head with a learned scale, then
  rotary positions ``x cos + rotate_half(x) sin`` at frequencies ``theta **
  (-2i / D)``; ``softmax(q k' / sqrt(D) + causal mask) v``; ``Op = W_o
  concat``.
* ``l < num_dense_layers`` - ``FF = W_2(silu(W_1 s) * W_3 s)``; else the
  sparse block: ``p = sigmoid(W_g s)``; ``S`` = the ``top_k`` largest of ``p
  + b`` (``jax.lax.top_k``; the bias ``b`` only selects); ``w_e = p_e /
  (sum_{j in S} p_j + 1e-6)`` times the scaling factor; ``FF = sum over the
  experts e held here, e in S, of w_e W2_e(silu(W1_e s) * W3_e s)``. The
  experts other chips hold add nothing, here as in the program.

Final RMSNorm, logits over the rows of the (tied) embedding held.

The round loop - data order, one client at a time, the float64 mean folded in
on the host leaf by leaf - is ``hybrid_lm_local_sgd.py``'s, loaded by path as
a module of its own whose ``make_step`` is this file's (its ``run_round``
looks the step up in its own globals; the file is not edited). Its
docstring says what that loop takes from the program (the data order, the
leaves' names, the module's hyperparameters) and why. Departures that the
builder knows of: layers and attention heads are rematerialised with
``jax.checkpoint`` in ``run_round`` so that the round fits a chip beside the
driver (same arithmetic); the tied embedding and the per-head q/k RMSNorm
are the family's published code, which the catalog's config has no key for.

``flops_per_row`` does not bill the masked-dense expert products written
here (every held expert on every token is ``num_experts / top_k`` times the
work a row needs): it counts the step by tracing with the sparse blocks
left out and adds them analytically at the balanced load, ``T x top_k x held
/ num_experts`` (token, choice) pairs a sparse layer x 3 products x ``2 x d x
w`` x 3 (forward and the two backward products), and the router's ``T x 2 x d
x num_experts`` x 3.
"""

from __future__ import annotations

import importlib.util
import math
import os
from typing import Dict

import jax
import jax.numpy as jnp

TASK = "lm_rows"


def _rms(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _short_conv(p, s):
    length, d = s.shape
    bcu = s @ p["in_proj"]
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    v = b * u
    conv = sum(jnp.concatenate([jnp.zeros((j, d), v.dtype),
                                v[:length - j]]) * p["conv_kernel"][j]
               for j in range(p["conv_kernel"].shape[0]))
    return (c * conv) @ p["out_proj"]


def _rope(x, theta):
    length, dim = x.shape
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    rotated = jnp.concatenate([-x[:, dim // 2:], x[:, :dim // 2]], axis=-1)
    return x * cos + rotated * sin


def _head(q, k, v, q_scale, k_scale, hp):
    """One query head against its key/value head: ``[T, D]`` each."""
    length, dim = q.shape
    q = _rope(_rms(q, q_scale, hp["eps"]), hp["rope_theta"])
    k = _rope(_rms(k, k_scale, hp["eps"]), hp["rope_theta"])
    pos = jnp.arange(length)
    scores = jnp.where(pos[None, :] <= pos[:, None],
                       q @ k.T / math.sqrt(dim), -jnp.inf)
    scores = scores - scores.max(-1, keepdims=True)
    e = jnp.exp(scores)
    return (e / e.sum(-1, keepdims=True)) @ v


def _attention(p, s, hp, remat):
    length = s.shape[0]
    dim, group = hp["head_dim"], hp["num_heads"] // hp["num_kv_heads"]

    def heads(w):  # [T, H * D] -> [H, T, D]
        return jnp.swapaxes((s @ w).reshape(length, -1, dim), 0, 1)

    q, k, v = heads(p["q_proj"]), heads(p["k_proj"]), heads(p["v_proj"])
    head = (lambda *a: _head(*a, hp))  # noqa: E731
    if remat:
        head = jax.checkpoint(head)

    def one(_, h):  # a loop over the query heads, one program for all
        return None, head(q[h], k[h // group], v[h // group],
                          p["q_norm_scale"], p["k_norm_scale"])

    _, outs = jax.lax.scan(one, None, jnp.arange(hp["num_heads"]))
    return jnp.swapaxes(outs, 0, 1).reshape(length, -1) @ p["o_proj"]


def _sparse(p, s, hp, remat: bool, experts: bool):
    if not experts:
        return jnp.zeros_like(s)
    prob = 1.0 / (1.0 + jnp.exp(-(s @ p["router"])))
    select = prob + p["expert_bias"] if "expert_bias" in p else prob
    _, chosen = jax.lax.top_k(select, hp["top_k"])
    picked = jnp.take_along_axis(prob, chosen, axis=-1)
    if hp["norm_topk"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    weights = picked * hp["scale"]
    first = hp["experts_held"][0]

    def one(out, expert):  # a loop over the experts held, each on every token
        i, w1, w3, w2 = expert
        w_e = jnp.where(chosen == first + i, weights, 0.0).sum(-1)
        return out + w_e[:, None] * ((_silu(s @ w1) * (s @ w3)) @ w2), None

    out, _ = jax.lax.scan(
        jax.checkpoint(one) if remat else one, jnp.zeros_like(s),
        (jnp.arange(p["experts_w1"].shape[0]), p["experts_w1"],
         p["experts_w3"], p["experts_w2"]))
    return out


def _layer(p, x, layer, hp, remat, experts):
    s = _rms(x, p["operator_norm_scale"], hp["eps"])
    if hp["layer_types"][layer] == "conv":
        x = x + _short_conv(p, s)
    else:
        x = x + _attention(p, s, hp, remat)
    s = _rms(x, p["ffn_norm_scale"], hp["eps"])
    if layer < hp["num_dense_layers"]:
        return x + (_silu(s @ p["ffn_w1"]) * (s @ p["ffn_w3"])) @ p["ffn_w2"]
    return x + _sparse(p, s, hp, remat, experts)


def hyperparameters(module) -> Dict:
    """The module's sizes, read as attributes."""
    return {"num_heads": int(module.num_heads),
            "num_kv_heads": int(module.num_kv_heads),
            "head_dim": int(module.hidden_size) // int(module.num_heads),
            "hidden": int(module.hidden_size),
            "width": int(module.moe_intermediate_size),
            "num_experts": int(module.num_experts),
            "top_k": int(module.num_experts_per_tok),
            "experts_held": tuple(int(i) for i in module.experts_held),
            "layers": tuple(int(i) for i in module.layer_ids),
            "layer_types": tuple(module.layer_types),
            "num_dense_layers": int(module.num_dense_layers),
            "rope_theta": float(module.rope_theta),
            "eps": float(module.norm_eps),
            "norm_topk": bool(module.norm_topk_prob),
            "scale": float(module.routed_scaling_factor)}


def logits_of(params, hp, tokens, remat: bool = False,
              experts: bool = True):
    """``[T, V]`` logits of one sequence of token ids ``[T]``; with
    ``experts`` false the sparse blocks add nothing (for the count of the
    other products)."""
    x = params["embedding"][tokens]
    for layer in hp["layers"]:
        fn = (lambda p, x, layer=layer:  # noqa: E731
              _layer(p, x, layer, hp, remat, experts))
        if remat:
            fn = jax.checkpoint(fn)
        x = fn(params[f"layer_{layer:02d}"], x)
    x = _rms(x, params["final_norm"]["norm_scale"], hp["eps"])
    return x @ params["embedding"].T


def _round_loop():
    """``hybrid_lm_local_sgd.py`` as a module of this file's own, stepping
    with this file's ``make_step``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "hybrid_lm_local_sgd.py")
    spec = importlib.util.spec_from_file_location(
        "_lfm2_moe_round_loop", path)
    loop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loop)
    loop.make_step = make_step
    return loop


def make_step(module, task: str, train: Dict, remat: bool,
              experts: bool = True):
    """One SGD step on one batch of rows: ``(params, x, y, mask, key) ->
    (params, loss_sum, count)``; the loss is the mean over the batch's real
    rows of each row's mean cross-entropy, ``key`` is unused (no dropout)."""
    if task != TASK:
        raise ValueError(f"the lfm2_moe_local_sgd reference has no "
                         f"{task!r} loss")
    if train.get("client_optimizer", "sgd") != "sgd":
        raise ValueError("the lfm2_moe_local_sgd reference is plain SGD")
    lr = float(train["lr"])
    hp = hyperparameters(module)

    def step(params, x, y, mask, key):
        del key

        def loss_fn(p):
            rows = jnp.stack([
                _LOOP.row_mean_cross_entropy(
                    logits_of(p, hp, x[i], remat, experts), y[i])
                for i in range(x.shape[0])])
            loss_sum, count = jnp.sum(rows * mask), jnp.sum(mask)
            return loss_sum / jnp.maximum(count, 1.0), (loss_sum, count)

        grads, (loss_sum, count) = jax.grad(loss_fn, has_aux=True)(params)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss_sum, count

    return step


_LOOP = _round_loop()
run_round = _LOOP.run_round


def flops_per_row(module, task: str, train: Dict, variables, sample_x,
                  count_flops) -> float:
    """Matrix-multiply FLOPs one training row (one packed sequence) needs,
    forward and backward: this reference's own step traced without
    rematerialisation and *without the sparse blocks*, plus the router's
    product and the experts' products at the balanced load (see the module
    docstring) - not the masked-dense products above, which would bill
    ``num_experts / top_k`` times the work."""
    _LOOP._only_params(variables)
    bsz = int(train["batch_size"])
    x = jnp.zeros((bsz,) + tuple(sample_x.shape[1:]), jnp.int32)
    mask = jnp.ones((bsz,), jnp.float32)
    step = make_step(module, task, train, remat=False, experts=False)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          variables["params"])
    traced = count_flops(step, shapes, x, x, mask, jax.random.key(0)) / bsz
    hp = hyperparameters(module)
    sparse = sum(layer >= hp["num_dense_layers"] for layer in hp["layers"])
    pairs = (x.shape[1] * hp["top_k"] * hp["experts_held"][1]
             / hp["num_experts"])
    block = (pairs * 3 * 2.0 * hp["hidden"] * hp["width"]
             + x.shape[1] * 2.0 * hp["hidden"] * hp["num_experts"])
    return traced + sparse * block * 3
