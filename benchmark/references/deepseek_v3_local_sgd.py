"""Plain reference of one FedAvg round of local SGD on the ``deepseek_v3``
decoder as kanana-2-30b-a3b-instruct-2601 publishes it (latent attention
without a query bottleneck, routed experts beside shared ones): the
architecture's own forward pass, its loss, gradient and SGD step, and the
weighted mean.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: attention head by head as whole
``[T, T]`` score matrices with an explicit mask, the routed experts as a loop
over the experts held, each applied to *every* token and weighted by the
token's routing weight (zero where the expert was not chosen), the shared
experts as one SwiGLU. The two loops - over the heads, over the experts held
- are ``lax.scan``s: one program for every head and expert. No sort, no
grouped product, no blocks, no ``module.apply``, nothing of ``fedml_tpu/ops``
or ``fedml_tpu/models``.

The layer equations (``x += MLA(RMSNorm(x))``, ``x += FF(RMSNorm'(x))``;
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale``; published layer ``l``):

* latent attention, ``H`` heads, no bias: ``q = s W_q`` is ``[q_nope |
  q_rope]`` a head; ``[c | k_rope] = s W_kv_a``: the latent and ONE rope key
  for all the heads; ``[k_nope | v] = RMSNorm(c) W_kv_b`` a head. Rotary
  positions over the rope channels alone, stored as pairs: ``(x_2i, x_2i+1)``
  turns by ``t theta ** (-2i / D)``. Head ``h``: ``softmax(([q_nope | q_rope]
  . [k_nope_h | k_rope]) * (nope + rope) ** -0.5 + causal mask) v_h``; ``W_o``
  over the concatenated heads.
* ``l < num_dense_layers`` - ``FF = W_2(silu(W_1 s) * W_3 s)``; else ``p =
  sigmoid(W_g s)``; ``S`` = the ``top_k`` largest of ``p + b``
  (``jax.lax.top_k``; the bias ``b`` only selects); ``w_e = p_e / (sum_{j in
  S} p_j + 1e-20)`` times the scaling factor; ``FF = sum over the experts e
  held here, e in S, of w_e W2_e(silu(W1_e s) * W3_e s)`` plus the shared
  experts' ``W_2(silu(W_1 s) * W_3 s)``, which every token passes. The
  routed experts other chips hold add nothing, here as in the program.

Final RMSNorm, logits over the rows held of the *untied* head ``lm_head``.

The round loop - data order, one client at a time, the float64 mean folded in
on the host leaf by leaf - is ``hybrid_lm_local_sgd.py``'s, loaded by path as
a module of its own whose ``make_step`` is this file's (as
``lfm2_moe_local_sgd.py`` does; that file's docstring says what the loop
takes from the program and why). Departures from the published code that the
builder knows of: layers, heads and experts are rematerialised with
``jax.checkpoint`` in ``run_round`` so that the round fits a chip beside the
driver (same arithmetic); the published code brings the rope channels to
half-split order and applies rotate-half - the pairwise rotation written here
gives the same channels in another order, the same for queries and keys, so
every score is the same; ``n_group`` 1 / ``topk_group`` 1 make the group
limit of ``noaux_tc`` the plain top-k written here; no ``mscale`` (the rope
scaling is null).

``flops_per_row`` does not bill what is written here where that is more than
a row needs: it traces the step with the routed experts and the attention
cores left out (every projection, the shared experts and the head once
forward and twice backward) and adds, a sparse layer, the router's ``T x 2 x
d x num_experts`` and the routed experts at the balanced load (``T x top_k x
held / num_experts`` pairs x 3 products x ``2 x d x w``), and a layer the
attention core at its **causal half**: ``T (T + 1) / 2`` (query, key) pairs x
``(qk + v) x 2`` x heads; all three times with the backward pass. The three
accepted references bill whole ``[T, T]`` score matrices as they write
them: this cell's ``mfu`` is not comparable with theirs in the attention
term (a third of this model's arithmetic, which doubling would flatter).
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict

import jax
import jax.numpy as jnp

TASK = "lm_rows"


def _rms(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _swiglu(s, w1, w3, w2):
    return (_silu(s @ w1) * (s @ w3)) @ w2


def _rope_pairs(x, theta):
    """``x [T, D]`` with every stored pair of channels ``(2i, 2i + 1)``
    turned by ``t theta ** (-2i / D)`` (``rope_interleave``)."""
    length, dim = x.shape
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, 0::2], x[:, 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(length, dim)


def _head(q, k, v):
    """One head: ``q, k [T, qk]``, ``v [T, v_dim]``, whole score matrix."""
    length, dim = q.shape
    pos = jnp.arange(length)
    scores = jnp.where(pos[None, :] <= pos[:, None],
                       (q @ k.T) * dim ** -0.5, -jnp.inf)
    scores = scores - scores.max(-1, keepdims=True)
    e = jnp.exp(scores)
    return (e / e.sum(-1, keepdims=True)) @ v


def _mla(p, s, hp, remat: bool, core: bool):
    length = s.shape[0]
    nope, rope, v_dim = hp["nope"], hp["rope"], hp["v_dim"]
    q = (s @ p["q_proj"]).reshape(length, -1, nope + rope)
    kv_a = s @ p["kv_a_proj"]
    latent, k_rope = kv_a[:, :hp["kv_rank"]], kv_a[:, hp["kv_rank"]:]
    kv = (_rms(latent, p["kv_norm_scale"], hp["eps"])
          @ p["kv_b_proj"]).reshape(length, -1, nope + v_dim)
    k_rope = _rope_pairs(k_rope, hp["rope_theta"])
    head = jax.checkpoint(_head) if remat else _head
    if not core:  # no product, and every projection still has its gradient
        head = (lambda q, k, v:  # noqa: E731
                v + q.sum(-1, keepdims=True) + k.sum(-1, keepdims=True))

    def one(_, h):  # a loop over the heads, one program for all
        q_h = jnp.concatenate([q[:, h, :nope], _rope_pairs(
            q[:, h, nope:], hp["rope_theta"])], -1)
        k_h = jnp.concatenate([kv[:, h, :nope], k_rope], -1)
        return None, head(q_h, k_h, kv[:, h, nope:])

    _, outs = jax.lax.scan(one, None, jnp.arange(hp["num_heads"]))
    return jnp.swapaxes(outs, 0, 1).reshape(length, -1) @ p["o_proj"]


def _routed(p, s, hp, remat: bool):
    prob = 1.0 / (1.0 + jnp.exp(-(s @ p["router"])))
    _, chosen = jax.lax.top_k(prob + p["expert_bias"], hp["top_k"])
    picked = jnp.take_along_axis(prob, chosen, axis=-1)
    if hp["norm_topk"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    weights = picked * hp["scale"]
    first = hp["experts_held"][0]

    def one(out, expert):  # a loop over the experts held, each on every token
        i, w1, w3, w2 = expert
        w_e = jnp.where(chosen == first + i, weights, 0.0).sum(-1)
        return out + w_e[:, None] * _swiglu(s, w1, w3, w2), None

    out, _ = jax.lax.scan(
        jax.checkpoint(one) if remat else one, jnp.zeros_like(s),
        (jnp.arange(p["experts_w1"].shape[0]), p["experts_w1"],
         p["experts_w3"], p["experts_w2"]))
    return out


def _layer(p, x, layer, hp, remat, experts, core):
    x = x + _mla(p, _rms(x, p["input_norm_scale"], hp["eps"]), hp, remat,
                 core)
    s = _rms(x, p["post_attention_norm_scale"], hp["eps"])
    if layer < hp["num_dense_layers"]:
        return x + _swiglu(s, p["ffn_w1"], p["ffn_w3"], p["ffn_w2"])
    y = _swiglu(s, p["shared_w1"], p["shared_w3"], p["shared_w2"])
    return x + y + (_routed(p, s, hp, remat) if experts else 0.0)


def hyperparameters(module) -> Dict:
    """The module's sizes, read as attributes."""
    return {"num_heads": int(module.num_heads),
            "nope": int(module.qk_nope_head_dim),
            "rope": int(module.qk_rope_head_dim),
            "v_dim": int(module.v_head_dim),
            "kv_rank": int(module.kv_lora_rank),
            "hidden": int(module.hidden_size),
            "width": int(module.moe_intermediate_size),
            "num_experts": int(module.n_routed_experts),
            "top_k": int(module.num_experts_per_tok),
            "experts_held": tuple(int(i) for i in module.experts_held),
            "layers": tuple(int(i) for i in module.layer_ids),
            "num_dense_layers": int(module.num_dense_layers),
            "rope_theta": float(module.rope_theta),
            "eps": float(module.rms_norm_eps),
            "norm_topk": bool(module.norm_topk_prob),
            "scale": float(module.routed_scaling_factor)}


def logits_of(params, hp, tokens, remat: bool = False, experts: bool = True,
              core: bool = True):
    """``[T, V]`` logits of one sequence of token ids ``[T]``; with
    ``experts`` false the routed experts add nothing and with ``core`` false
    an attention core is a sum without a product (for the count of the other
    products)."""
    x = params["embedding"][tokens]
    for layer in hp["layers"]:
        fn = (lambda p, x, layer=layer:  # noqa: E731
              _layer(p, x, layer, hp, remat, experts, core))
        if remat:
            fn = jax.checkpoint(fn)
        x = fn(params[f"layer_{layer:02d}"], x)
    x = _rms(x, params["final_norm"]["norm_scale"], hp["eps"])
    return x @ params["lm_head"].T


def _round_loop():
    """``hybrid_lm_local_sgd.py`` as a module of this file's own, stepping
    with this file's ``make_step``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "hybrid_lm_local_sgd.py")
    spec = importlib.util.spec_from_file_location(
        "_deepseek_v3_round_loop", path)
    loop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loop)
    loop.make_step = make_step
    return loop


def make_step(module, task: str, train: Dict, remat: bool,
              experts: bool = True, core: bool = True):
    """One SGD step on one batch of rows: ``(params, x, y, mask, key) ->
    (params, loss_sum, count)``; the loss is the mean over the batch's real
    rows of each row's mean cross-entropy, ``key`` is unused (no dropout)."""
    if task != TASK:
        raise ValueError(f"the deepseek_v3_local_sgd reference has no "
                         f"{task!r} loss")
    if train.get("client_optimizer", "sgd") != "sgd":
        raise ValueError("the deepseek_v3_local_sgd reference is plain SGD")
    lr = float(train["lr"])
    hp = hyperparameters(module)

    def step(params, x, y, mask, key):
        del key

        def loss_fn(p):
            rows = jnp.stack([
                _LOOP.row_mean_cross_entropy(
                    logits_of(p, hp, x[i], remat, experts, core), y[i])
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
    rematerialisation, *without the routed experts and the attention cores*,
    plus the router, the routed experts at the balanced load and the cores at
    their causal half (see the module docstring)."""
    _LOOP._only_params(variables)
    bsz = int(train["batch_size"])
    x = jnp.zeros((bsz,) + tuple(sample_x.shape[1:]), jnp.int32)
    mask = jnp.ones((bsz,), jnp.float32)
    step = make_step(module, task, train, remat=False, experts=False,
                     core=False)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          variables["params"])
    traced = count_flops(step, shapes, x, x, mask, jax.random.key(0)) / bsz
    hp = hyperparameters(module)
    length = x.shape[1]
    sparse = sum(layer >= hp["num_dense_layers"] for layer in hp["layers"])
    pairs = length * hp["top_k"] * hp["experts_held"][1] / hp["num_experts"]
    block = (pairs * 3 * 2.0 * hp["hidden"] * hp["width"]
             + length * 2.0 * hp["hidden"] * hp["num_experts"])
    core = (length * (length + 1) / 2.0 * 2.0 * hp["num_heads"]
            * (hp["nope"] + hp["rope"] + hp["v_dim"]))
    return traced + 3.0 * (sparse * block + len(hp["layers"]) * core)
