"""Task heads: loss + metric kernels for the three federated task families.

The reference hardwires these into per-task trainer subclasses
(fedml_api/standalone/fedavg/my_model_trainer_{classification,nwp,
tag_prediction}.py and the stackoverflow_lr branch in
fedml_api/distributed/fedavg/MyModelTrainer.py:72-83). Here each head is a
pure function ``head(logits, targets, mask) -> stat sums`` so it can run
inside jit/vmap/shard_map; all stats are *sums* (not means) so they aggregate
correctly across batches, clients and mesh shards by plain addition / psum.

Masking convention: every example row carries a 0/1 ``mask`` weight (padding
rows are 0). Sequence heads additionally mask padding tokens inside each
example. The per-batch training loss is ``loss_sum / count`` — identical to
torch's reduction='mean' over the real examples in the batch.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.models.decoder import RoutedTiedHead, TiedHead

Stats = Dict[str, jnp.ndarray]
TaskHead = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], Stats]

PAD_TOKEN = 0  # sequence pad id (LEAF/TFF convention: 0-padded batches)


def classification_head(logits: jnp.ndarray, targets: jnp.ndarray,
                        mask: jnp.ndarray) -> Stats:
    """Softmax CE + top-1 accuracy. logits [B, C], integer targets [B]."""
    per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    correct = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
    return {
        "loss_sum": jnp.sum(per_ex * mask),
        "count": jnp.sum(mask),
        "correct_sum": jnp.sum(correct * mask),
    }


def nwp_head(logits: jnp.ndarray, targets: jnp.ndarray,
             mask: jnp.ndarray) -> Stats:
    """Next-word/char prediction: per-token CE over [B, T, V] logits.

    The accounting unit is the *token* (reference my_model_trainer_nwp
    counts correct tokens and divides by token totals); pad tokens
    (``PAD_TOKEN``) and padded example rows are excluded.
    """
    per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    tok_mask = (targets != PAD_TOKEN).astype(jnp.float32) * mask[:, None]
    correct = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
    return {
        "loss_sum": jnp.sum(per_tok * tok_mask),
        "count": jnp.sum(tok_mask),
        "correct_sum": jnp.sum(correct * tok_mask),
    }


def _routing_stats(expert_load, block_rows, mask) -> Stats:
    """``moe_assignments``: the pairs of the real rows that landed on held
    experts, all sparse layers; ``moe_top_expert_assignments``: per sparse
    layer the most loaded held expert's pairs, summed; ``moe_block_rows``:
    the rows the grouped products ran, all sparse layers. Sums like every
    stat, so ``held x top / assignments`` over any span of steps is the
    load-weighted peak-to-mean ratio, 1.0 when balanced, and ``assignments /
    block rows`` the share of the rows run that were real pairs."""
    load = jnp.einsum("b,ble->le", mask, jax.lax.stop_gradient(expert_load))
    return {"moe_assignments": jnp.sum(load),
            "moe_top_expert_assignments": jnp.sum(jnp.max(load, axis=-1,
                                                          initial=0.0)),
            "moe_block_rows": jnp.sum(jax.lax.stop_gradient(block_rows))}


#: positions whose logits ``lm_rows_head`` holds at a time
LOGIT_BLOCK = 512


def _position_stats(logits, targets):
    """(cross-entropy, top-1 hit) of every position; logits [..., V]."""
    logits = logits.astype(jnp.float32)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    return per_tok, (jnp.argmax(logits, -1) == targets).astype(jnp.float32)


def _tied_position_stats(hidden, embedding, targets):
    """``_position_stats`` of a tied head's logits, formed ``LOGIT_BLOCK``
    positions at a time, each block rematerialised."""
    rows, length, _ = hidden.shape
    block = min(LOGIT_BLOCK, length)
    pad = (-length) % block

    def blocks(a):
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape((rows, -1, block) + a.shape[2:])
        return jnp.swapaxes(a, 0, 1)

    @jax.checkpoint
    def one(inp):
        h, t = inp
        return _position_stats(jnp.einsum("btd,vd->btv", h, embedding), t)

    def rejoin(a):
        return jnp.swapaxes(a, 0, 1).reshape(rows, -1)[:, :length]

    return jax.tree.map(rejoin, jax.lax.map(
        one, (blocks(hidden), blocks(targets))))


def lm_rows_head(out, targets: jnp.ndarray, mask: jnp.ndarray) -> Stats:
    """Language modelling where the accounting unit is the *row*: one packed
    sequence whose ``T`` positions all carry a target (no pad id). Per row
    the mean cross-entropy over its targets; ``loss_sum`` sums the real
    rows, ``count`` counts them, ``correct_sum`` sums their mean top-1
    accuracy - all equal to the token means because every row has exactly
    ``T`` targets, and in the unit the drivers count cohorts in.

    ``out`` is ``[B, T, V]`` logits or a :class:`TiedHead`, for which the
    logits are formed ``LOGIT_BLOCK`` positions at a time, each block
    rematerialised, so neither pass holds more than one block of them. A
    :class:`RoutedTiedHead` adds the three routing sums of
    ``_routing_stats``; any other output gets the three keys alone."""
    routing = {}
    if isinstance(out, RoutedTiedHead):
        routing = _routing_stats(out.expert_load, out.block_rows, mask)
        out = TiedHead(out.hidden, out.embedding)
    with jax.named_scope("fedml.lm_head"):
        per_tok, correct = (_tied_position_stats(*out, targets)
                            if isinstance(out, TiedHead)
                            else _position_stats(out, targets))
    return {
        "loss_sum": jnp.sum(jnp.mean(per_tok, axis=-1) * mask),
        "count": jnp.sum(mask),
        "correct_sum": jnp.sum(jnp.mean(correct, axis=-1) * mask),
        **routing,
    }


def tag_prediction_head(logits: jnp.ndarray, targets: jnp.ndarray,
                        mask: jnp.ndarray) -> Stats:
    """Multi-label tag prediction (stackoverflow_lr): sigmoid BCE.

    Metrics mirror MyModelTrainer.py:72-83: an example is "correct" only when
    every label matches at threshold 0.5; precision/recall are per-example
    ratios summed over examples (averaged by the caller via ``count``).
    """
    per_label = optax.sigmoid_binary_cross_entropy(logits, targets)
    per_ex = jnp.mean(per_label, axis=-1)
    pred = (jax.nn.sigmoid(logits) > 0.5).astype(jnp.float32)
    exact = jnp.all(pred == targets, axis=-1).astype(jnp.float32)
    tp = jnp.sum(pred * targets, axis=-1)
    precision = tp / (jnp.sum(pred, axis=-1) + 1e-13)
    recall = tp / (jnp.sum(targets, axis=-1) + 1e-13)
    return {
        "loss_sum": jnp.sum(per_ex * mask),
        "count": jnp.sum(mask),
        "correct_sum": jnp.sum(exact * mask),
        "precision_sum": jnp.sum(precision * mask),
        "recall_sum": jnp.sum(recall * mask),
    }


# -- segmentation heads (reference fedseg SegmentationLosses, utils.py:71) --

IGNORE_INDEX = 255  # Pascal-VOC convention: pixels excluded from loss/metrics


def _pixel_mask(targets: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Valid-pixel weights: example mask x (target != ignore_index)."""
    valid = (targets != IGNORE_INDEX).astype(jnp.float32)
    return valid * mask.reshape(mask.shape + (1,) * (targets.ndim - 1))


def segmentation_head(logits, targets, mask) -> Stats:
    """Mean per-valid-pixel CE (SegmentationLosses.CrossEntropyLoss)."""
    safe_targets = jnp.where(targets == IGNORE_INDEX, 0, targets)
    per_px = optax.softmax_cross_entropy_with_integer_labels(logits,
                                                             safe_targets)
    pm = _pixel_mask(targets, mask)
    correct = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
    return {"loss_sum": jnp.sum(per_px * pm), "count": jnp.sum(pm),
            "correct_sum": jnp.sum(correct * pm)}


def segmentation_focal_head(logits, targets, mask, gamma: float = 2.0,
                            alpha: float = 0.5) -> Stats:
    """Focal loss: -alpha * (1-pt)^gamma * log pt per valid pixel
    (SegmentationLosses.FocalLoss, utils.py:95-109)."""
    safe_targets = jnp.where(targets == IGNORE_INDEX, 0, targets)
    logpt = -optax.softmax_cross_entropy_with_integer_labels(logits,
                                                             safe_targets)
    pt = jnp.exp(logpt)
    per_px = -((1.0 - pt) ** gamma) * alpha * logpt
    pm = _pixel_mask(targets, mask)
    correct = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
    return {"loss_sum": jnp.sum(per_px * pm), "count": jnp.sum(pm),
            "correct_sum": jnp.sum(correct * pm)}


TASK_HEADS: Dict[str, TaskHead] = {
    "classification": classification_head,
    "nwp": nwp_head,
    "lm_rows": lm_rows_head,
    "tag_prediction": tag_prediction_head,
    "segmentation": segmentation_head,
    "segmentation_focal": segmentation_focal_head,
}


def stats_to_metrics(stats: Stats, prefix: str = "test") -> Dict[str, float]:
    """Convert device stat sums to the reference metrics dict shape
    (MyModelTrainer.test: test_correct/test_loss/test_total...)."""
    out = {
        f"{prefix}_correct": float(stats["correct_sum"]),
        f"{prefix}_loss": float(stats["loss_sum"]),
        f"{prefix}_total": float(stats["count"]),
    }
    if "precision_sum" in stats:
        out[f"{prefix}_precision"] = float(stats["precision_sum"])
        out[f"{prefix}_recall"] = float(stats["recall_sum"])
    return out
