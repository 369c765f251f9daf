"""Plain reference of one FedAvg round of local SGD on the Granite-4.0-H
hybrid decoder (granite-4.0-h-micro, ``model_type`` ``granitemoehybrid`` with
no experts): the architecture's own forward pass, its loss, gradient and SGD
step, and the weighted mean.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: **the Mamba-2 state recurrence step
by step** (``lax.scan`` over the positions, the state ``[H, P, N]`` advanced
one token at a time - no chunks, no dual form, no matrix product in it),
attention head by head as whole ``[T, T]`` score matrices with an explicit
mask (a ``lax.scan`` over the 32 query heads: the heads are the blocks of
rows of the ``[32 T, T]`` scores it is computed in, so that one head's 16 MB
is held at a time), the convolution as shifted products, the logits of a
whole row at once. No ``module.apply``, nothing of ``fedml_tpu/ops`` or
``fedml_tpu/models``.

The equations, for a row ``tokens [T]`` and published layer ``l``
(``RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w``)::

    x = embedding_multiplier * E[tokens]
    x = x + residual_multiplier * Mixer_l(RMSNorm(x; w_in))
    [a, b] = RMSNorm(x; w_post) W_in
    x = x + residual_multiplier * (silu(a) * b) W_out
    logits = RMSNorm(x; w_final) E' / logits_scaling

* ``layer_types[l] == "attention"``: per query head ``h`` (key/value head
  ``h // (H / H_kv)``), no bias, no positions: ``softmax(q k' *
  attention_multiplier + causal mask) v``; ``W_o concat``.
* ``"mamba"`` (Mamba-2, arXiv:2405.21060): ``[z | xBC | dt] = s W_in``;
  ``xBC_t = silu(bias + sum_{j < 4} k_j * xBC_{t-j})`` per channel (zeros
  before the row); ``[xs | B | C] = xBC``, ``xs`` as ``H`` heads of ``P``,
  ``B`` and ``C`` as ``G`` groups of ``N`` (head ``h`` reads group ``h // (H
  / G)``); ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` a head;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t xs_t outer B_t`` from ``S = 0``; ``y_t =
  S_t C_t + D xs_t``; ``Mixer = RMSNorm(y * silu(z); w_gate) W_out`` over all
  ``H P`` channels, the gate first.

The round loop - data order, one client at a time, the float64 mean folded in
on the host leaf by leaf - is ``hybrid_lm_local_sgd.py``'s, loaded by path as
a module of its own whose ``make_step`` is this file's (its ``run_round``
looks the step up in its own globals; the file is not edited). Its docstring
says what that loop takes from the program (the data order, the leaves'
names, the module's hyperparameters) and why.

Departures from the published code that the builder knows of: in
``run_round`` layers and attention heads are rematerialised with
``jax.checkpoint`` and the recurrence is cut into runs of 64 positions, each
rematerialised, so that the backward pass holds 64 states of ``[H, P, N]``
and not the row's 2,048 (4.3 GB a layer at the published widths) - the steps
and their order are the same; the order of the input projection's parts and
of ``x | B | C``, the gate before the norm, ``head_dim = hidden / heads`` and
the tied embedding are the family's published code, which the catalog's
config has no key for; ``dt`` is not clamped (the published limits are 0 and
infinity).

``flops_per_row`` bills the step as traced without rematerialisation - every
projection once forward and twice backward, attention as the whole ``[T, T]``
matrices written here - and, because the recurrence above is elementwise and
traces to nothing, adds the state-space layers at **the dual form's
products**: a token and Mamba-2 layer forward ``2 Q N G`` (``C B'`` over a
chunk of ``Q`` positions, once a group) ``+ 2 Q P H`` (the masked scores
applied to the inputs) ``+ 4 N P H`` (the chunk's state built, the incoming
state read), three times that with the backward pass.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict

import jax
import jax.numpy as jnp

TASK = "lm_rows"
#: positions a rematerialised run of the recurrence holds (``run_round``)
_RUN = 64


def _rms(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _recurrence(xs, dt, a, b, c, remat: bool):
    """``y [T, H, P]``: ``xs [T, H, P]``, ``dt [T, H]``, ``a [H]``, ``b`` and
    ``c [T, H, N]`` (a head's group's), one position at a time."""

    def step(state, inputs):
        xt, dt_t, bt, ct = inputs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * xt)[:, :, None] * bt[:, None, :])
        return state, (state * ct[:, None, :]).sum(-1)

    zero = jnp.zeros(xs.shape[1:] + (b.shape[-1],), xs.dtype)
    length = xs.shape[0]
    if not remat or length % _RUN:
        return jax.lax.scan(step, zero, (xs, dt, b, c))[1]

    def run(state, inputs):
        return jax.lax.scan(step, state, inputs)

    runs = tuple(x.reshape((-1, _RUN) + x.shape[1:])
                 for x in (xs, dt, b, c))
    y = jax.lax.scan(jax.checkpoint(run), zero, runs)[1]
    return y.reshape(xs.shape)


def _mamba2(p, s, hp, remat):
    length = s.shape[0]
    heads, dim = hp["mamba_heads"], hp["mamba_head_dim"]
    groups, state = hp["mamba_groups"], hp["mamba_state"]
    inner, shared = heads * dim, groups * state
    zxbcdt = s @ p["in_proj"]
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * shared]
    dt = zxbcdt[:, 2 * inner + 2 * shared:]
    conv = p["conv_bias"] + sum(
        jnp.concatenate([jnp.zeros((j, xbc.shape[1]), xbc.dtype),
                         xbc[:length - j]]) * p["conv_kernel"][j]
        for j in range(p["conv_kernel"].shape[0]))
    xbc = _silu(conv)
    xs = xbc[:, :inner].reshape(length, heads, dim)
    per_head = heads // groups
    b = jnp.repeat(xbc[:, inner:inner + shared].reshape(
        length, groups, state), per_head, axis=1)
    c = jnp.repeat(xbc[:, inner + shared:].reshape(
        length, groups, state), per_head, axis=1)
    dt = _softplus(dt + p["dt_bias"])
    y = _recurrence(xs, dt, -jnp.exp(p["a_log"]), b, c, remat)
    y = (y + p["d_skip"][:, None] * xs).reshape(length, inner)
    return _rms(y * _silu(z), p["gate_norm_scale"], hp["eps"]) \
        @ p["out_proj"]


def _head(q, k, v, scale):
    """One query head against its key/value head: ``[T, D]`` each."""
    pos = jnp.arange(q.shape[0])
    scores = jnp.where(pos[None, :] <= pos[:, None], q @ k.T * scale,
                       -jnp.inf)
    scores = scores - scores.max(-1, keepdims=True)
    e = jnp.exp(scores)
    return (e / e.sum(-1, keepdims=True)) @ v


def _attention(p, s, hp, remat):
    length = s.shape[0]
    dim, group = hp["head_dim"], hp["num_heads"] // hp["num_kv_heads"]

    def heads(w):  # [T, H * D] -> [H, T, D]
        return jnp.swapaxes((s @ w).reshape(length, -1, dim), 0, 1)

    q, k, v = heads(p["q_proj"]), heads(p["k_proj"]), heads(p["v_proj"])
    head = (lambda *a: _head(*a, hp["attention_multiplier"]))  # noqa: E731
    if remat:
        head = jax.checkpoint(head)

    def one(_, h):  # a loop over the query heads, one program for all
        return None, head(q[h], k[h // group], v[h // group])

    _, outs = jax.lax.scan(one, None, jnp.arange(hp["num_heads"]))
    return jnp.swapaxes(outs, 0, 1).reshape(length, -1) @ p["o_proj"]


def _layer(p, x, layer, hp, remat):
    s = _rms(x, p["input_norm_scale"], hp["eps"])
    if hp["layer_types"][layer] == "mamba":
        mixed = _mamba2(p, s, hp, remat)
    else:
        mixed = _attention(p, s, hp, remat)
    x = x + hp["residual_multiplier"] * mixed
    ab = _rms(x, p["post_norm_scale"], hp["eps"]) @ p["ffn_in"]
    half = ab.shape[1] // 2
    return x + hp["residual_multiplier"] * (
        (_silu(ab[:, :half]) * ab[:, half:]) @ p["ffn_out"])


def hyperparameters(module) -> Dict:
    """The module's sizes, read as attributes."""
    return {"num_heads": int(module.num_heads),
            "num_kv_heads": int(module.num_kv_heads),
            "head_dim": int(module.hidden_size) // int(module.num_heads),
            "layers": tuple(int(i) for i in module.layer_ids),
            "layer_types": tuple(module.layer_types),
            "mamba_heads": int(module.mamba_n_heads),
            "mamba_head_dim": int(module.mamba_d_head),
            "mamba_state": int(module.mamba_d_state),
            "mamba_groups": int(module.mamba_n_groups),
            "mamba_chunk": int(module.mamba_chunk_size),
            "embedding_multiplier": float(module.embedding_multiplier),
            "residual_multiplier": float(module.residual_multiplier),
            "attention_multiplier": float(module.attention_multiplier),
            "logits_scaling": float(module.logits_scaling),
            "eps": float(module.rms_norm_eps)}


def logits_of(params, hp, tokens, remat: bool = False):
    """``[T, V]`` logits of one sequence of token ids ``[T]``."""
    x = hp["embedding_multiplier"] * params["embedding"][tokens]
    for layer in hp["layers"]:
        fn = (lambda p, x, layer=layer:  # noqa: E731
              _layer(p, x, layer, hp, remat))
        if remat:
            fn = jax.checkpoint(fn)
        x = fn(params[f"layer_{layer:02d}"], x)
    x = _rms(x, params["final_norm"]["norm_scale"], hp["eps"])
    return x @ params["embedding"].T / hp["logits_scaling"]


def _round_loop():
    """``hybrid_lm_local_sgd.py`` as a module of this file's own, stepping
    with this file's ``make_step``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "hybrid_lm_local_sgd.py")
    spec = importlib.util.spec_from_file_location(
        "_granite_hybrid_round_loop", path)
    loop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loop)
    loop.make_step = make_step
    return loop


def make_step(module, task: str, train: Dict, remat: bool):
    """One SGD step on one batch of rows: ``(params, x, y, mask, key) ->
    (params, loss_sum, count)``; the loss is the mean over the batch's real
    rows of each row's mean cross-entropy, ``key`` is unused (no dropout)."""
    if task != TASK:
        raise ValueError(f"the granite_hybrid_local_sgd reference has no "
                         f"{task!r} loss")
    if train.get("client_optimizer", "sgd") != "sgd":
        raise ValueError("the granite_hybrid_local_sgd reference is plain "
                         "SGD")
    lr = float(train["lr"])
    hp = hyperparameters(module)

    def step(params, x, y, mask, key):
        del key

        def loss_fn(p):
            rows = jnp.stack([
                _LOOP.row_mean_cross_entropy(
                    logits_of(p, hp, x[i], remat), y[i])
                for i in range(x.shape[0])])
            loss_sum, count = jnp.sum(rows * mask), jnp.sum(mask)
            return loss_sum / jnp.maximum(count, 1.0), (loss_sum, count)

        grads, (loss_sum, count) = jax.grad(loss_fn, has_aux=True)(params)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss_sum, count

    return step


_LOOP = _round_loop()
run_round = _LOOP.run_round


def ssd_flops_per_token(hp) -> float:
    """The dual form's products of one token in one Mamba-2 layer,
    forward (see the module docstring)."""
    chunk, state = hp["mamba_chunk"], hp["mamba_state"]
    heads, dim = hp["mamba_heads"], hp["mamba_head_dim"]
    return (2.0 * chunk * state * hp["mamba_groups"]
            + 2.0 * chunk * dim * heads + 4.0 * state * dim * heads)


def flops_per_row(module, task: str, train: Dict, variables, sample_x,
                  count_flops) -> float:
    """Matrix-multiply FLOPs one training row (one packed sequence) needs,
    forward and backward: this reference's own step traced without
    rematerialisation - the recurrence is elementwise there and counts
    nothing - plus the state-space layers at the dual form's products, three
    times the forward count."""
    _LOOP._only_params(variables)
    bsz = int(train["batch_size"])
    x = jnp.zeros((bsz,) + tuple(sample_x.shape[1:]), jnp.int32)
    mask = jnp.ones((bsz,), jnp.float32)
    step = make_step(module, task, train, remat=False)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          variables["params"])
    traced = count_flops(step, shapes, x, x, mask, jax.random.key(0)) / bsz
    hp = hyperparameters(module)
    mamba = sum(hp["layer_types"][layer] == "mamba"
                for layer in hp["layers"])
    return traced + 3.0 * mamba * x.shape[1] * ssd_flops_per_token(hp)
