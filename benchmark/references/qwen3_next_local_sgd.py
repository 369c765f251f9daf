"""Plain reference of one FedAvg round of local SGD on the ``qwen3_next``
decoder as Qwen3-Next-80B-A3B-Instruct publishes it (Gated DeltaNet linear
attention in three layers of four, gated full attention in the fourth,
routed experts by a softmax router beside a gated shared expert): the
architecture's own forward pass, its loss, gradient and SGD step, and the
weighted mean.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: the delta rule step by step
(``lax.scan`` over positions), attention head by head as whole ``[T, T]``
score matrices with an explicit mask, the routed experts as a loop over the
experts held, each applied to *every* token and weighted by the token's
routing weight (zero where the expert was not chosen). The loops over the
heads and over the experts held are ``lax.scan``s: one program for every
head and expert. No chunking, no sort, no grouped product, no blocks, no
``module.apply``, nothing of ``fedml_tpu/ops`` or ``fedml_tpu/models``.

The layer equations (``x += Mix(RMSNorm0(x))``, ``x += Sparse(RMSNorm0'(x))``;
``RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``; published layer
``l``, full attention where ``(l + 1) % full_attention_interval == 0``):

* Gated DeltaNet, ``Hk`` key heads of ``dk``, ``H`` value heads of ``dv``,
  ``r = H / Hk``: ``s W_qkvz`` per key head ``[q | k | r values | r gates
  z]``, ``s W_ba`` per key head ``[r betas | r decays]``; ``[q | k | v] =
  silu(causal depthwise conv(q | k | v))`` (no bias, tap ``j`` of ``K``
  weighs the input ``K - 1 - j`` positions back); ``q, k`` over
  ``sqrt(sum of squares + 1e-6)`` a head, ``q`` times ``dk ** -0.5``;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; value
  head ``j`` reads key head ``j // r``, from ``S = 0``: ``S = exp(g_t) S;
  S = S + k_t (beta_t (v_t - S' k_t))'; o_t = S' q_t``; ``o = RMSNorm(o_j;
  w_n) * silu(z_j)`` (the norm first, a scale shared by the heads), then
  ``W_out``.
* gated attention, ``Hq`` query heads, ``Hkv`` key/value heads of ``D``:
  ``s W_q`` a head ``[q | gate]``; ``q = RMSNorm0(q)``, ``k = RMSNorm0(s W_k)``
  a head; rotate-half rotary positions over the first ``D x
  partial_rotary_factor`` channels (pairs ``(i, i + rot / 2)`` turn by ``t
  theta ** (-2 i / rot)``), the others pass; head ``h``: ``softmax(q_h
  k_{h // (Hq / Hkv)}' D ** -0.5 + causal mask) v_{h // (Hq / Hkv)}``; ``W_o``
  over the concatenated heads times ``sigmoid(gate)``.
* sparse: ``p = softmax(s W_r)`` over all the experts; ``S`` the ``top_k``
  largest; ``w_e = p_e / sum_{j in S} p_j``; ``Sparse = sum over the experts
  e held here, e in S, of w_e W2_e(silu(W1_e s) * W3_e s) + sigmoid(s w_sg)
  W_2(silu(W_1 s) * W_3 s)``. The routed experts other chips hold add
  nothing, here as in the program.

Final ``RMSNorm0``, logits over the rows held of the *untied* head
``lm_head``.

The round loop - data order, one client at a time, the float64 mean folded in
on the host leaf by leaf - is ``hybrid_lm_local_sgd.py``'s, loaded by path as
a module of its own whose ``make_step`` is this file's (as
``deepseek_v3_local_sgd.py`` does). Departures from the published code: the
multi-token-prediction module the family describes is left out (no key of
the config names it, and the causal-LM loss does not run it); the
per-key-head layout of ``W_qkvz`` and ``W_ba`` is taken as the published
code splits them; layers, heads and experts are rematerialised with
``jax.checkpoint`` in ``run_round``, and the delta rule's scan keeps its
state every ``SEGMENT`` steps and recomputes the steps between, so that a row
of 2,048 positions through 32 heads of ``[128, 128]`` fits a chip beside the
driver (the arithmetic is the same).

``flops_per_row`` does not bill what is written here where that is more than
a row needs: it traces the step with the routed experts, the attention cores
and the delta rules left out (every projection, the shared expert, its gate
and the head once forward and twice backward) and adds, a layer, the
router's ``T x 2 x d x num_experts`` and the routed experts at the expected
held load (``T x top_k x held / num_experts`` pairs x 3 products x ``2 x d x
w``); a full layer the attention core at its **causal half** (``T (T + 1) /
2`` pairs x ``2 x 2 D`` x ``Hq``); all three times with the backward pass;
and the delta rules as ``benchmark/kernels/gated_delta.py::cost`` bills
them (its chunked form, the same function the roofline bills), never the
step recurrence written here.
"""

from __future__ import annotations

import importlib.util
import math
import os
from typing import Dict

import jax
import jax.numpy as jnp

TASK = "lm_rows"
#: positions of the delta rule between two kept states
SEGMENT = 64
_HERE = os.path.dirname(os.path.abspath(__file__))


def _rms(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def _rms0(x, weight, eps):
    return _rms(x, 1.0 + weight, eps)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _swiglu(s, w1, w3, w2):
    return (_silu(s @ w1) * (s @ w3)) @ w2


def _delta_rule(q, k, v, g, beta, remat: bool):
    """``o [T, H, dv]`` step by step from a zero state; ``q, k [T, H, dk]``
    (one a value head), ``v [T, H, dv]``, ``g, beta [T, H]``."""
    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[:, None, None] * state
        said = (state * k_t[:, :, None]).sum(1)           # S' k, [H, dv]
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - said))[
            :, None, :]
        return state, (state * q_t[:, :, None]).sum(1)

    length, heads, dv = v.shape
    state = jnp.zeros((heads, k.shape[2], dv), v.dtype)
    inputs = (q, k, v, g, beta)
    segment = math.gcd(length, SEGMENT)
    if not remat or segment == length:
        return jax.lax.scan(step, state, inputs)[1]

    @jax.checkpoint
    def run(state, xs):  # one segment: only its first state is kept
        return jax.lax.scan(step, state, xs)

    _, out = jax.lax.scan(run, state, jax.tree.map(
        lambda a: a.reshape((-1, segment) + a.shape[1:]), inputs))
    return out.reshape(v.shape)


def _linear(p, s, hp, remat: bool, core: bool):
    length = s.shape[0]
    key_heads, heads, dk, dv = (hp["key_heads"], hp["value_heads"], hp["dk"],
                                hp["dv"])
    group = heads // key_heads
    qkvz = (s @ p["in_proj_qkvz"]).reshape(length, key_heads, -1)
    q = qkvz[..., :dk]
    k = qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + group * dv]
    z = qkvz[..., 2 * dk + group * dv:].reshape(length, heads, dv)
    ba = (s @ p["in_proj_ba"]).reshape(length, key_heads, 2 * group)
    b = ba[..., :group].reshape(length, heads)
    a = ba[..., group:].reshape(length, heads)
    mixed = jnp.concatenate([q.reshape(length, -1), k.reshape(length, -1),
                             v.reshape(length, -1)], -1)
    taps = p["conv_kernel"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, mixed.shape[1])), mixed])
    mixed = _silu(sum(padded[j:j + length] * p["conv_kernel"][j]
                      for j in range(taps)))
    q = mixed[:, :key_heads * dk].reshape(length, key_heads, dk)
    k = mixed[:, key_heads * dk:2 * key_heads * dk].reshape(
        length, key_heads, dk)
    v = mixed[:, 2 * key_heads * dk:].reshape(length, heads, dv)
    q = q / jnp.sqrt((q ** 2).sum(-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k / jnp.sqrt((k ** 2).sum(-1, keepdims=True) + 1e-6)
    q, k = jnp.repeat(q, group, axis=1), jnp.repeat(k, group, axis=1)
    beta = _sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jnp.logaddexp(a + p["dt_bias"], 0.0)
    if core:
        o = _delta_rule(q, k, v, g, beta, remat)
    else:  # no product, and every projection still has its gradient
        o = v + (q.sum(-1) + k.sum(-1) + g + beta)[..., None]
    o = _rms(o, p["norm_scale"], hp["eps"]) * _silu(z)
    return o.reshape(length, -1) @ p["out_proj"]


def _rope_part(x, theta, rot):
    """Rotate-half positions over the first ``rot`` channels of ``x [T,
    D]``; the others pass."""
    length = x.shape[0]
    half = rot // 2
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[:, rot:]],
                           -1)


def _head(q, k, v):
    """One head: ``q, k [T, D]``, ``v [T, D]``, whole score matrix."""
    length, dim = q.shape
    pos = jnp.arange(length)
    scores = jnp.where(pos[None, :] <= pos[:, None],
                       (q @ k.T) * dim ** -0.5, -jnp.inf)
    scores = scores - scores.max(-1, keepdims=True)
    e = jnp.exp(scores)
    return (e / e.sum(-1, keepdims=True)) @ v


def _attention(p, s, hp, remat: bool, core: bool):
    length, dim = s.shape[0], hp["head_dim"]
    qg = (s @ p["q_proj"]).reshape(length, -1, 2 * dim)
    q, gate = qg[..., :dim], qg[..., dim:]
    k = (s @ p["k_proj"]).reshape(length, -1, dim)
    v = (s @ p["v_proj"]).reshape(length, -1, dim)
    q = _rms0(q, p["q_norm_scale"], hp["eps"])
    k = _rms0(k, p["k_norm_scale"], hp["eps"])
    group = q.shape[1] // k.shape[1]
    head = jax.checkpoint(_head) if remat else _head
    if not core:
        head = (lambda q, k, v:  # noqa: E731
                v + q.sum(-1, keepdims=True) + k.sum(-1, keepdims=True))

    def one(_, h):  # a loop over the query heads, one program for all
        turn = (lambda x: _rope_part(x, hp["rope_theta"],  # noqa: E731
                                     hp["rotary_dim"]))
        return None, head(turn(q[:, h]), turn(k[:, h // group]),
                          v[:, h // group])

    _, outs = jax.lax.scan(one, None, jnp.arange(q.shape[1]))
    out = jnp.swapaxes(outs, 0, 1)                     # [T, Hq, D]
    return (out * _sigmoid(gate)).reshape(length, -1) @ p["o_proj"]


def _routed(p, s, hp, remat: bool):
    logits = s @ p["router"]
    e = jnp.exp(logits - logits.max(-1, keepdims=True))
    prob = e / e.sum(-1, keepdims=True)
    picked, chosen = jax.lax.top_k(prob, hp["top_k"])
    if hp["norm_topk"]:
        picked = picked / picked.sum(-1, keepdims=True)
    first = hp["experts_held"][0]

    def one(out, expert):  # a loop over the experts held, each on every token
        i, w1, w3, w2 = expert
        w_e = jnp.where(chosen == first + i, picked, 0.0).sum(-1)
        return out + w_e[:, None] * _swiglu(s, w1, w3, w2), None

    out, _ = jax.lax.scan(
        jax.checkpoint(one) if remat else one, jnp.zeros_like(s),
        (jnp.arange(p["experts_w1"].shape[0]), p["experts_w1"],
         p["experts_w3"], p["experts_w2"]))
    return out


def _shared(p, s):
    return _sigmoid(s @ p["shared_gate"]) * _swiglu(
        s, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def _is_full(layer: int, hp) -> bool:
    return (layer + 1) % hp["interval"] == 0


def _layer(p, x, layer, hp, remat, experts, core):
    mixer = _attention if _is_full(layer, hp) else _linear
    x = x + mixer(p, _rms0(x, p["input_norm_scale"], hp["eps"]), hp, remat,
                  core)
    s = _rms0(x, p["post_attention_norm_scale"], hp["eps"])
    return x + _shared(p, s) + (_routed(p, s, hp, remat) if experts else 0.0)


def hyperparameters(module) -> Dict:
    """The module's sizes, read as attributes."""
    return {"hidden": int(module.hidden_size),
            "head_dim": int(module.head_dim),
            "num_heads": int(module.num_attention_heads),
            "rotary_dim": int(int(module.head_dim)
                              * float(module.partial_rotary_factor)),
            "rope_theta": float(module.rope_theta),
            "key_heads": int(module.linear_num_key_heads),
            "value_heads": int(module.linear_num_value_heads),
            "dk": int(module.linear_key_head_dim),
            "dv": int(module.linear_value_head_dim),
            "width": int(module.moe_intermediate_size),
            "num_experts": int(module.num_experts),
            "top_k": int(module.num_experts_per_tok),
            "norm_topk": bool(module.norm_topk_prob),
            "experts_held": tuple(int(i) for i in module.experts_held),
            "layers": tuple(int(i) for i in module.layer_ids),
            "interval": int(module.full_attention_interval),
            "eps": float(module.rms_norm_eps)}


def logits_of(params, hp, tokens, remat: bool = False, experts: bool = True,
              core: bool = True):
    """``[T, V]`` logits of one sequence of token ids ``[T]``; with
    ``experts`` false the routed experts add nothing and with ``core`` false
    an attention core or a delta rule is a sum without a product (for the
    count of the other products)."""
    x = params["embedding"][tokens]
    for layer in hp["layers"]:
        fn = (lambda p, x, layer=layer:  # noqa: E731
              _layer(p, x, layer, hp, remat, experts, core))
        if remat:
            fn = jax.checkpoint(fn)
        x = fn(params[f"layer_{layer:02d}"], x)
    x = _rms0(x, params["final_norm"]["norm_scale"], hp["eps"])
    return x @ params["lm_head"].T


def _load(name: str, *parts):
    """A file of the benchmark as a module of this file's own."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_HERE, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _round_loop():
    """``hybrid_lm_local_sgd.py`` as a module of this file's own, stepping
    with this file's ``make_step``."""
    loop = _load("_qwen3_next_round_loop", "hybrid_lm_local_sgd.py")
    loop.make_step = make_step
    return loop


def make_step(module, task: str, train: Dict, remat: bool,
              experts: bool = True, core: bool = True):
    """One SGD step on one batch of rows: ``(params, x, y, mask, key) ->
    (params, loss_sum, count)``; the loss is the mean over the batch's real
    rows of each row's mean cross-entropy, ``key`` is unused (no dropout)."""
    if task != TASK:
        raise ValueError(f"the qwen3_next_local_sgd reference has no "
                         f"{task!r} loss")
    if train.get("client_optimizer", "sgd") != "sgd":
        raise ValueError("the qwen3_next_local_sgd reference is plain SGD")
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
    rematerialisation, *without the routed experts, the attention cores and
    the delta rules*, plus the router, the routed experts at the expected
    held load, the cores at their causal half and the delta rules as
    ``kernels/gated_delta.py`` bills them (see the module docstring)."""
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
    full = sum(_is_full(layer, hp) for layer in hp["layers"])
    pairs = length * hp["top_k"] * hp["experts_held"][1] / hp["num_experts"]
    block = (pairs * 3 * 2.0 * hp["hidden"] * hp["width"]
             + length * 2.0 * hp["hidden"] * hp["num_experts"])
    core = (length * (length + 1) / 2.0 * 2.0 * hp["num_heads"]
            * 2 * hp["head_dim"])
    delta, _ = _load("_qwen3_next_delta_cost", os.pardir, "kernels",
                     "gated_delta.py").cost(
        length, hp["key_heads"], hp["value_heads"], hp["dk"], hp["dv"],
        len(hp["layers"]) - full)
    return traced + 3.0 * (len(hp["layers"]) * block + full * core) + delta
