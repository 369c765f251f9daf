"""Pure, jittable local-training and evaluation programs.

This is the TPU-native replacement for the reference's hot loop
(fedml_api/distributed/fedavg/MyModelTrainer.py:19-49: python epochs × torch
DataLoader batches). Here one client's whole local-training pass —
``epochs × batches`` of forward/CE/backward/SGD — is a single ``lax.scan``
over a precomputed (epoch-shuffled) index array of padded batches, so XLA
compiles it into one fused device program. Under ``jax.vmap`` it trains every
sampled client simultaneously (standalone simulation); under ``shard_map`` it
becomes the per-shard body of the distributed SPMD round. A caller that
knows how far its clients' real batches reach (the sim driver's tiers of a
ragged cohort, algorithms/fedavg.py) passes that as ``n_steps`` and gets the
same steps from a loop that stops there.

Data layout per client: flat padded arrays ``x: [n_pad, ...]``, ``y``,
``mask: [n_pad]`` with ``n_pad`` a multiple of the batch size; the mask
weights the loss so padding rows contribute zero gradient and the per-batch
loss equals torch's mean over the real examples.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.models.common import (cut_window, dead_taps, live_windows,
                                     write_window)
from fedml_tpu.trainer.tasks import TASK_HEADS, TaskHead


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Local-training hyperparameters (reference argparse flags:
    --epochs --batch_size --client_optimizer --lr --wd)."""

    epochs: int = 1
    batch_size: Optional[int] = None  # None = full batch (one step per epoch)
    lr: float = 0.03
    client_optimizer: str = "sgd"  # "sgd" | "adam"
    wd: float = 0.0
    momentum: float = 0.0
    shuffle: bool = True
    # mixed precision: run forward/backward in this dtype (e.g. "bfloat16"
    # — the MXU's native input type) while master params, optimizer state,
    # loss, and metrics stay float32. None = pure f32 (parity tests).
    compute_dtype: Optional[str] = None
    # gradient accumulation: average grads over k consecutive micro-batches
    # before each optimizer step (effective batch = k * batch_size at the
    # HBM footprint of one micro-batch)
    accum_steps: int = 1
    # per-ROUND exponential client-LR decay: effective lr at round r is
    # ``lr * lr_decay_round ** r``. 1.0 = constant lr (the reference's only
    # mode — its argparse has no schedule; FedAvg-paper-style decay is the
    # standard fix for the constant-LR late-round overfit tail seen on the
    # fed_cifar100 anchor). Exact, not approximate: the client optimizer is
    # reconstructed fresh each round (reference MyModelTrainer.py:26-31
    # semantics) and lr enters optax's sgd/adam updates as a final
    # multiplicative scale, so scaling the round's updates by decay**r IS
    # running the round at lr*decay**r.
    lr_decay_round: float = 1.0


def validate_accum_steps(cfg: TrainConfig, client_sizes) -> None:
    """Host-side accum_steps guard: MultiSteps emits an optimizer update
    only on every k-th REAL micro-batch (padding-only batches are gated
    no-ops), so a client whose ``epochs * ceil(n_i / bsz)`` is not a
    multiple of ``accum_steps`` silently drops its trailing micro-batches
    (worst case: zero optimizer steps). The real batch count is per-client
    data the traced trainer cannot see — drivers that know the federation's
    sizes call this at construction."""
    if cfg.accum_steps <= 1:
        return
    bad = {}
    for c, n in dict(client_sizes).items():
        bsz = cfg.batch_size or n
        # an empty client has zero real batches -> zero optimizer steps,
        # which accum_steps>1 cannot fix; flag it rather than divide by 0
        real_steps = cfg.epochs * -(-n // bsz) if bsz else 0
        if real_steps % cfg.accum_steps != 0:
            bad[c] = real_steps
    if bad:
        some = dict(list(bad.items())[:5])
        raise ValueError(
            f"accum_steps={cfg.accum_steps} must divide every client's "
            f"epochs*ceil(n_i/batch_size); offending clients (first 5 of "
            f"{len(bad)}): {some} — trailing real micro-batches would be "
            "silently dropped")


def round_lr_scale(cfg: TrainConfig, round_idx):
    """In-graph per-round client-LR scale ``lr_decay_round ** round_idx``,
    or None when the schedule is off (so constant-LR programs are traced
    without the extra multiply). ``round_idx`` may be a host int or a traced
    scalar (fused drivers derive it inside the round scan); the f32 power is
    computed the same way on every path so host-loop and fused trajectories
    stay bit-identical."""
    if cfg.lr_decay_round == 1.0:
        return None
    return jnp.power(jnp.float32(cfg.lr_decay_round),
                     jnp.asarray(round_idx).astype(jnp.float32))


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    """Client optimizer factory, matching the reference's two choices
    (MyModelTrainer.py:26-31): plain SGD, or Adam(amsgrad) with L2-style
    weight decay folded into the gradient like torch's ``weight_decay``."""
    def wrap(tx: optax.GradientTransformation):
        if cfg.accum_steps > 1:
            return optax.MultiSteps(tx, every_k_schedule=cfg.accum_steps)
        return tx

    if cfg.client_optimizer == "sgd":
        if cfg.momentum:
            return wrap(optax.sgd(cfg.lr, momentum=cfg.momentum))
        return wrap(optax.sgd(cfg.lr))
    if cfg.client_optimizer == "adam":
        steps = []
        if cfg.wd:
            steps.append(optax.add_decayed_weights(cfg.wd))
        steps.append(optax.amsgrad(cfg.lr))
        return wrap(optax.chain(*steps))
    raise ValueError(f"unknown client_optimizer: {cfg.client_optimizer!r}")


def make_forward(module) -> Callable:
    """Uniform apply over a variables dict {'params', [other collections]}.

    Returns ``(outputs, updated_collections)``; in train mode non-param
    collections (e.g. flax ``batch_stats``) are mutable, mirroring how the
    reference ships the *full* state_dict (weights + BN running stats) through
    aggregation (FedAVGAggregator.py:58-87 averages every key).
    """

    def forward(variables, x, train: bool, rng=None):
        rngs = {"dropout": rng} if (train and rng is not None) else None
        mutable = [k for k in variables if k != "params"]
        if train:
            out, updates = module.apply(variables, x, train=True, rngs=rngs,
                                        mutable=mutable)
            return out, {**variables, **updates}
        out = module.apply(variables, x, train=False)
        return out, variables

    return forward


def make_batch_schedule(n_pad: int, epochs: int, bsz: int, shuffle: bool,
                        rng, mask=None):
    """Shared epochs×batches schedule: per-epoch permutations reshaped to
    [epochs*nb, bsz] index batches plus one dropout key per step. Used by the
    FedAvg local trainer and custom local trainers (FedNova) so shuffle
    semantics cannot diverge.

    The schedule is PADDING-INVARIANT: row ``i``'s sort key is derived from
    ``fold_in(epoch_key, i)`` alone, and padding rows (``mask == 0``) sort
    last, so the order restricted to real rows — and therefore the whole
    trajectory — is identical for every ``n_pad`` the caller packs to. This
    is what lets cohort-bucket packing, global packing, and fused R-round
    blocks (one static shape for R cohorts) share one trajectory. It is
    also the reference's DataLoader semantics: full real batches, then one
    partial boundary batch, then pure-padding batches that the trainers
    gate into no-ops (local_train's ``has_real``); the reference shuffles
    only real samples (torch DataLoader(shuffle=True),
    MyModelTrainer.py:19-49)."""
    assert n_pad % bsz == 0, "data must be padded to a batch multiple"
    nb = n_pad // bsz
    perm_key, step_key = jax.random.split(rng)
    epoch_keys = jax.random.split(perm_key, epochs)
    rows = jnp.arange(n_pad)
    if shuffle:
        def epoch_perm(k):
            vals = jax.vmap(
                lambda i: jax.random.bits(jax.random.fold_in(k, i)))(rows)
            if mask is not None:
                # padding last; ties resolve by row index (stable argsort),
                # and real rows always have lower indices than padding
                vals = jnp.where(mask > 0, vals, jnp.uint32(0xFFFFFFFF))
            return jnp.argsort(vals)
        perms = jax.vmap(epoch_perm)(epoch_keys)
    else:
        # pack_clients lays real rows first, so the identity order already
        # has padding last
        perms = jnp.tile(rows, (epochs, 1))
    batch_idx = perms.reshape(epochs * nb, bsz)
    # step (dropout) keys are per (epoch, batch-position): batch b of epoch
    # e gets the same key at every n_pad, keeping stochastic layers on the
    # padding-invariant trajectory too
    step_keys = jax.vmap(
        lambda ek: jax.vmap(lambda b: jax.random.fold_in(ek, b))(
            jnp.arange(nb)))(jax.random.split(step_key, epochs))
    return batch_idx, step_keys.reshape(epochs * nb)


def real_batches(mask, cfg: TrainConfig):
    """How many leading batches of each epoch of ``make_batch_schedule``
    hold a real row of this client; every later batch is pure padding, a
    step ``local_train`` gates into a no-op. A shuffled schedule sorts the
    padding rows last, so the real rows fill the first ``ceil(real / bsz)``
    batches; the identity order reaches as far as the last real row."""
    bsz = cfg.batch_size or mask.shape[0]
    real = mask > 0
    if cfg.shuffle:
        rows = jnp.sum(real)
    else:
        rows = jnp.max(jnp.where(real, jnp.arange(1, mask.shape[0] + 1), 0))
    return (rows + bsz - 1) // bsz


def leaves_zero_gradients_alone(cfg: TrainConfig) -> bool:
    """Whether ``cfg``'s optimizer answers a zero gradient with an update
    of exactly zero, step after step, read off one scalar parameter over two
    accumulation cycles: true of SGD with or without momentum and of
    amsgrad (under ``MultiSteps`` too), false once ``add_decayed_weights``
    moves a parameter by its own value."""
    tx = make_optimizer(cfg)
    with jax.ensure_compile_time_eval():
        param, zero = jnp.ones(()), jnp.zeros(())
        state = tx.init(param)
        for _ in range(2 * cfg.accum_steps):
            update, state = tx.update(zero, state, param)
            if float(update) != 0.0:
                return False
    return True


def carried_windows(module, cfg: TrainConfig, variables, rows) -> dict:
    """``{path in variables["params"]: window}`` of the kernels whose
    window alone ``make_local_train``'s loop carries on batches shaped like
    ``rows``: the model's ``live_windows`` there, and none where
    ``cfg``'s optimizer moves a parameter whose gradient is zero."""
    windows = live_windows(module, variables, rows)
    return windows if windows and leaves_zero_gradients_alone(cfg) else {}


def carried_params(module, cfg: TrainConfig, variables, rows) -> int:
    """Parameters a client's local loop carries through its steps: the
    model's count less the dead taps outside ``carried_windows``, which it
    leaves at the global model's value."""
    params = variables["params"]
    return sum(leaf.size for leaf in jax.tree.leaves(params)) - dead_taps(
        carried_windows(module, cfg, variables, rows), params)


def _at_windows(fn, windows, tree, *rest):
    """``fn(leaf, *leaves of rest, window)`` at every path of ``tree`` that
    ``windows`` names (``models/common.py::live_windows``), the last tree's
    leaf elsewhere, in ``tree``'s own containers."""
    def one(path, *leaves):
        window = windows.get(tuple(k.key for k in path))
        return leaves[-1] if window is None else fn(*leaves, window)

    return jax.tree_util.tree_map_with_path(one, tree, *rest)


def make_local_train(module, task: str, cfg: TrainConfig,
                     grad_sync_axes: tuple = ()):
    """Build ``local_train(variables, x, y, mask, rng) -> (variables, stats)``.

    One call = the reference's ``ModelTrainer.train`` for one client: fresh
    optimizer (the reference constructs a new torch optimizer every call, so
    client momentum never crosses rounds), ``cfg.epochs`` passes with per-epoch
    reshuffling, mask-weighted per-batch mean loss.

    ``grad_sync_axes``: mesh axis names this client's model is itself
    sharded over inside a ``shard_map`` (e.g. ('seq',) for sequence-parallel
    clients): per-step loss terms and gradients are psum'd over them so
    every shard takes the identical optimizer step.

    ``local_train(..., n_steps=k)`` (a traced scalar, the same for every
    client of a ``vmap``) runs only the first ``k`` batches of each epoch:
    exact whenever ``k >= real_batches(mask, cfg)``, because the batches it
    leaves out are the gated no-ops. Without it the loop is the ``scan``
    over all ``n_pad // batch_size`` batches.

    ``local_train(..., shared_init=True)`` (static, like ``n_steps`` the
    caller's to know) says that ``variables`` start clients that run one
    after another (``make_folded_body``'s loop over the silos), so they
    have to outlive this one. A step loop updates its carry in place and
    XLA would first copy every leaf into it, once a client; instead the
    first step is taken before the loop - it reads the caller's leaves and
    its update writes this client's - and the ``scan`` runs the steps that
    remain (none left: no loop). The same ``step``, batches and keys in the
    same order, the first step's stats joined to the stacked ones: the
    arithmetic is the whole-length scan's. Not with ``n_steps``, whose
    callers train their clients side by side.

    What the loop carries: of every kernel the model declares a live window
    of at these rows' shape (``models/common.py::live_windows``: a padded
    convolution on a map smaller than its kernel) the window alone -
    parameters, optimizer state, gradient, update and the ``has_real``
    select - and the whole of every other leaf. A tap outside the window
    only ever meets zero padding, so its gradient is zero by construction
    and a client leaves it at the global model's value: the returned leaf
    is the global one with the trained window written into it, once, after
    the last step (the parent's shapes and values, dead taps to the bit).
    That is exact only where a zero gradient means a zero update, which is
    asked of the optimizer itself (``carried_windows``; not true of
    adam with weight decay): otherwise, and for every model without such a
    window, whole leaves are carried.
    """
    from fedml_tpu.utils import on_tpu

    # every driver (sim, spmd, cross-silo, mesh) builds its trainer here,
    # so here the one backend rule refuses a backend nobody chose — a cpu
    # JAX fell back to must not train and exit 0
    on_tpu()
    head: TaskHead = TASK_HEADS[task]
    forward = make_forward(module)
    tx = make_optimizer(cfg)
    cdtype = jnp.dtype(cfg.compute_dtype) if cfg.compute_dtype else None

    def _to_compute(tree):
        return jax.tree.map(
            lambda a: a.astype(cdtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    def _to_f32(tree):
        return jax.tree.map(
            lambda a: a.astype(jnp.float32)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    # the scope names the trainer's operations in a device trace; it is
    # location metadata and changes no instruction
    @jax.named_scope("fedml.local_train")
    def local_train(variables, x, y, mask, rng, lr_scale=None, n_steps=None,
                    shared_init=False):
        if shared_init and n_steps is not None:
            raise ValueError(
                "shared_init takes the first step out of the whole-length "
                "scan; the n_steps loop has no such step to take out")
        n_pad = x.shape[0]
        bsz = cfg.batch_size or n_pad
        # accum_steps divisibility cannot be checked here: only REAL
        # batches advance MultiSteps (padding-only batches are has_real
        # no-ops), and the real count is per-client data, not the static
        # n_pad. Drivers that know client sizes call
        # validate_accum_steps() host-side instead.
        batch_idx, step_keys = make_batch_schedule(n_pad, cfg.epochs, bsz,
                                                   cfg.shuffle, rng,
                                                   mask=mask)
        # the part of each leaf a step at this row shape can change, as
        # the model declares it; nothing is computed for the question
        windows = carried_windows(
            module, cfg, variables,
            jax.ShapeDtypeStruct((bsz,) + x.shape[1:], x.dtype))
        params = variables["params"]
        if windows:
            params = _at_windows(cut_window, windows, params)
        opt_state = tx.init(params)
        init = (params, {k: v for k, v in variables.items() if k != "params"},
                opt_state)

        def step(carry, inp):
            params, colls, opt_state = carry
            idx, key = inp
            xb = jnp.take(x, idx, axis=0)
            yb = jnp.take(y, idx, axis=0)
            mb = jnp.take(mask, idx, axis=0)

            def loss_fn(p):
                if cdtype is not None:
                    # bf16 forward/backward off f32 masters: the cast is on
                    # the autodiff path, so grads come back f32; updated
                    # collections (BN stats) are restored to f32 to keep the
                    # scan carry type stable
                    out, new_vars = forward(
                        {"params": _to_compute(p), **_to_compute(colls)},
                        _to_compute(xb), True, key)
                    out = _to_f32(out)
                    new_vars = _to_f32(new_vars)
                else:
                    out, new_vars = forward({"params": p, **colls}, xb,
                                            True, key)
                stats = head(out, yb, mb)
                if grad_sync_axes:
                    # differentiate the UNNORMALIZED local loss sum and
                    # keep every psum outside the grad: the client's loss
                    # is psum(loss_sum)/psum(count), whose gradient is
                    # psum(d loss_sum/dθ)/psum(count) because count does
                    # not depend on θ — so syncing and normalizing after
                    # jax.grad is exact, and it sidesteps the psum
                    # transpose entirely (pre-VMA jax transposes psum to
                    # psum, which would scale in-grad-synced gradients by
                    # the axis size)
                    loss = stats["loss_sum"]
                else:
                    loss = stats["loss_sum"] / jnp.maximum(stats["count"],
                                                           1.0)
                return loss, (new_vars, stats)

            grads, (new_vars, stats) = jax.grad(loss_fn, has_aux=True)(params)
            if grad_sync_axes:
                # each shard's backward holds only its tokens' terms of
                # d[loss_sum]/dθ; the psum + global-count normalization
                # completes the exact full-sequence gradient on every shard
                stats = jax.tree.map(
                    lambda s: jax.lax.psum(s, grad_sync_axes), stats)
                denom = jnp.maximum(stats["count"], 1.0)
                grads = jax.tree.map(
                    lambda g: g / denom,
                    jax.lax.psum(grads, grad_sync_axes))
            updates, new_opt_state = tx.update(grads, opt_state, params)
            if lr_scale is not None:
                # round-level lr schedule (TrainConfig.lr_decay_round):
                # exact because the optimizer is fresh per call and lr is a
                # final multiplicative scale in sgd/adam updates
                updates = jax.tree.map(lambda u: u * lr_scale, updates)
            new_params = optax.apply_updates(params, updates)
            # padding-only batches (small client, dataset-wide n_pad) must be
            # true no-ops: zero grads still move stateful optimizers
            # (weight decay, momentum, adam count), so gate the whole update
            has_real = stats["count"] > 0

            def sel(new, old):
                return jax.tree.map(lambda a, b: jnp.where(has_real, a, b),
                                    new, old)

            params = sel(new_params, params)
            opt_state = sel(new_opt_state, opt_state)
            colls = sel({k: v for k, v in new_vars.items() if k != "params"},
                        colls)
            return (params, colls, opt_state), stats

        if shared_init:
            # the first step reads the caller's leaves and writes this
            # client's own; the loop carries those, in place, from there
            state, first = step(init, (batch_idx[0], step_keys[0]))
            stats = jax.tree.map(lambda s: s[None], first)
            if batch_idx.shape[0] > 1:
                state, rest = jax.lax.scan(
                    step, state, (batch_idx[1:], step_keys[1:]))
                stats = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b]), stats, rest)
            params, colls, _ = state
        elif n_steps is None:
            (params, colls, _), stats = jax.lax.scan(
                step, init, (batch_idx, step_keys))
        else:
            # a traced bound makes this a ``while`` whose predicate is a
            # scalar even under ``vmap``; iteration i is batch i % n_steps
            # of epoch i // n_steps, the scan's batch with the scan's key
            nb = n_pad // bsz
            n_steps = jnp.minimum(n_steps, nb).astype(jnp.int32)

            def bounded_step(i, carry):
                state, stats = carry
                at = (i // n_steps) * nb + i % n_steps
                state, new = step(state, (batch_idx[at], step_keys[at]))
                return state, jax.tree.map(lambda s, v: s.at[at].set(v),
                                           stats, new)

            # the scan's stacked per-step stats, zero where no step ran
            # (what a pure-padding batch reads), so their sum is the
            # scan's to the bit
            zeros = jax.tree.map(
                lambda s: jnp.zeros((cfg.epochs * nb,) + s.shape, s.dtype),
                jax.eval_shape(step, init, (batch_idx[0], step_keys[0]))[1])
            (params, colls, _), stats = jax.lax.fori_loop(
                0, cfg.epochs * n_steps, bounded_step, (init, zeros))
        total = jax.tree.map(lambda s: jnp.sum(s, axis=0), stats)
        if windows:
            params = _at_windows(write_window, windows,
                                 variables["params"], params)
        return {"params": params, **colls}, total

    return local_train


#: positions an evaluation batch holds at most where a row is a sequence of
#: token ids: 512 rows of 2,048 positions would be a million positions'
#: activations (and their logits) at once
EVAL_BATCH_TOKENS = 8192


def make_eval(module, task: str, eval_batch_size: int = 512):
    """Build ``evaluate(variables, x, y, mask) -> stat sums`` that scans fixed
    eval batches (deterministic mode, no dropout), the jittable analogue of
    the reference's ``ModelTrainer.test`` loop (MyModelTrainer.py:51-96).
    A batch is ``eval_batch_size`` rows, or where the rows are sequences of
    token ids (integer ``[n, T]``) as many as hold ``EVAL_BATCH_TOKENS``
    positions, if that is fewer."""
    head: TaskHead = TASK_HEADS[task]
    forward = make_forward(module)

    @jax.named_scope("fedml.eval")
    def evaluate(variables, x, y, mask):
        n = x.shape[0]
        if n == 0:
            # empty eval set: run the head once on a zero dummy batch with a
            # zero mask so the stat keys exist and all sums are 0
            dummy_x = jnp.zeros((1,) + x.shape[1:], x.dtype)
            dummy_y = jnp.zeros((1,) + y.shape[1:], y.dtype)
            out, _ = forward(variables, dummy_x, False)
            return head(out, dummy_y, jnp.zeros((1,), jnp.float32))
        bsz = min(eval_batch_size, n)
        if x.ndim == 2 and jnp.issubdtype(x.dtype, jnp.integer):
            bsz = max(1, min(bsz, EVAL_BATCH_TOKENS // x.shape[1]))
        n_pad = ((n + bsz - 1) // bsz) * bsz
        pad = n_pad - n
        if pad:
            x_p = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
            y_p = jnp.pad(y, [(0, pad)] + [(0, 0)] * (y.ndim - 1))
            m_p = jnp.pad(mask, (0, pad))
        else:
            x_p, y_p, m_p = x, y, mask
        nb = n_pad // bsz
        xb = x_p.reshape((nb, bsz) + x.shape[1:])
        yb = y_p.reshape((nb, bsz) + y.shape[1:])
        mb = m_p.reshape(nb, bsz)

        def step(carry, batch):
            bx, by, bm = batch
            out, _ = forward(variables, bx, False)
            stats = head(out, by, bm)
            return carry, stats

        _, stats = jax.lax.scan(step, 0, (xb, yb, mb))
        return jax.tree.map(lambda s: jnp.sum(s, axis=0), stats)

    return evaluate
