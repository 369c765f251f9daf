"""The ``token_silos`` generator: a federation of silos that each hold a few
long packed token sequences, made in bulk from ``--seed``.

``build(data, clients, seed) -> (FederatedDataset, training rows per
client)``. It reads ``sequence_length``, ``vocab``, ``train_rows``,
``test_rows``, ``zipf_s`` and ``follow_share`` from the configuration's
``data`` block. A row is one packed sequence: ``sequence_length + 1`` tokens
are drawn, the inputs are the first ``sequence_length`` of them and the
targets the same tokens shifted by one, so every position carries a target
and there is no pad id.

Token ids lie in ``[0, vocab)``. A silo draws ranks from a Zipf law
(``p(rank) ~ rank ** -zipf_s``) and maps them to ids through a permutation
of its own, so the silos' unigram statistics differ (non-IID); with
probability ``follow_share`` a token is instead the *successor* of the one
before it under one fixed map shared by every silo (``succ(t) = (48271 t +
11) mod vocab``), the next-token dependency that lets the loss fall. Every
seed gives the same shapes; the content and which rows a silo holds change
with the seed.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def _sequences(rng: np.random.Generator, rows: int, length: int, vocab: int,
               cdf: np.ndarray, follow_share: float) -> np.ndarray:
    """``[rows, length]`` token ids of one silo."""
    ids = rng.permutation(vocab)[
        np.searchsorted(cdf, rng.random((rows, length)))]
    follow = rng.random((rows, length)) < follow_share
    for t in range(1, length):
        succ = (ids[:, t - 1].astype(np.int64) * 48271 + 11) % vocab
        ids[:, t] = np.where(follow[:, t], succ, ids[:, t])
    return ids.astype(np.int32)


def build(data: Dict, clients: int, seed: int) -> Tuple[object, np.ndarray]:
    from fedml_tpu.data.base import FederatedDataset

    length, vocab = int(data["sequence_length"]), int(data["vocab"])
    n_train, n_test = int(data["train_rows"]), int(data["test_rows"])
    weights = np.arange(1, vocab + 1, dtype=np.float64) ** -float(
        data["zipf_s"])
    cdf = np.cumsum(weights / weights.sum())
    cdf[-1] = 1.0
    train, test = {}, {}
    for c, rng in enumerate(np.random.default_rng(seed).spawn(clients)):
        rows = _sequences(rng, n_train + n_test, length + 1, vocab, cdf,
                          float(data["follow_share"]))
        train[c] = (rows[:n_train, :-1], rows[:n_train, 1:])
        test[c] = (rows[n_train:, :-1], rows[n_train:, 1:])
    dataset = FederatedDataset.from_client_arrays(train, test,
                                                  class_num=vocab)
    return dataset, np.full(clients, n_train, np.int64)
