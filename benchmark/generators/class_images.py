"""The ``class_images`` generator: a federation of class-conditional images,
made in bulk from ``--seed``.

A generator is a file ``generators/<name>.py`` with one function,
``build(data, clients, seed) -> (FederatedDataset, training rows per
client)``; a configuration names its generator in ``data["generator"]`` and
the rest of that block is the generator's own. This one reads ``image_hw``,
``channels``, ``classes``, ``sizes``, ``test_fraction``,
``dominant_classes``, ``pixel_noise`` and ``label_ceiling``. It keeps the
shape facts of the program's own generators
(``fedml_tpu/data/flagship_gen.py``: client counts, size spread, image
shape, dominant-class skew, pixel noise and the label-noise ceiling) and
drops their per-client Python loop: every array is made in one pass over all
samples (seconds where the loop takes 6-22 s), and nothing is cached on
disk.

Two departures from the program's generator, both for steadiness:

* client sizes are the *quantiles* of the configured distribution, dealt to
  the clients by a seeded permutation, not independent draws. Every seed
  then has the same multiset of sizes — the same total rows, the same
  largest client, hence the same compiled shapes and the same evaluation
  set size — while the cohort a round samples still differs with the seed;
* a client's training and test rows are generated as two blocks (the
  content is i.i.d. given client and class, so which rows are held out
  changes nothing), which lets the union arrays be the storage and every
  client's shard a view into them.
"""

from __future__ import annotations

import os
import statistics
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import numpy as np

#: samples per generated chunk. Each chunk has a generator of its own,
#: spawned from the seed by the chunk's index, so the content does not
#: depend on how many threads fill the chunks
_CHUNK = 4096
_THREADS = min(8, os.cpu_count() or 1)


def client_sizes(spec: Dict, clients: int, rng: np.random.Generator
                 ) -> np.ndarray:
    """Total samples per client: the ``clients`` mid-quantiles of
    ``spec``'s distribution in a seeded order."""
    kind = spec["kind"]
    if kind == "uniform":
        return np.full(clients, int(spec["n"]), np.int64)
    if kind == "lognormal_clipped":
        inv = statistics.NormalDist().inv_cdf
        z = np.array([inv((i + 0.5) / clients) for i in range(clients)])
        raw = spec["offset"] + np.exp(spec["mu"] + spec["sigma"] * z)
        sizes = np.clip(raw.astype(np.int64), spec["min"], spec["max"])
        return rng.permutation(sizes)
    raise ValueError(f"unknown size distribution {kind!r}")


def split_sizes(spec: Dict, clients: int, rng: np.random.Generator
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(training rows, test rows) per client: ``test_fraction`` of each
    client's samples, at least one, is held out."""
    sizes = client_sizes(spec["sizes"], clients, rng)
    n_test = np.maximum(1, (sizes * float(spec["test_fraction"])
                            ).astype(np.int64))
    return sizes - n_test, n_test


def _prototypes(rng: np.random.Generator, classes: int, hw: int,
                chans: int) -> np.ndarray:
    """Per-class smooth patterns in [0, 1]: products of two cosines with
    seeded frequencies and phases plus a class-keyed diagonal wave."""
    yy, xx = np.mgrid[0:hw, 0:hw].astype(np.float64) / hw
    f = rng.integers(1, 5, (2, classes, chans, 1, 1))
    p = rng.random((2, classes, chans, 1, 1)) * 2 * np.pi
    img = (np.cos(2 * np.pi * f[0] * xx + p[0])
           * np.cos(2 * np.pi * f[1] * yy + p[1]))
    wave = (np.arange(classes) % 7 + 1)[:, None, None, None]
    img = img + 0.5 * np.cos(2 * np.pi * (xx + yy) * wave
                             + np.arange(chans)[None, :, None, None])
    lo = img.min(axis=(2, 3), keepdims=True)
    hi = img.max(axis=(2, 3), keepdims=True)
    img = (img - lo) / (hi - lo + 1e-12)
    return np.ascontiguousarray(
        img.transpose(0, 2, 3, 1).astype(np.float32))  # [C, hw, hw, ch]


def _block(rng: np.random.Generator, owner: np.ndarray,
           class_order: np.ndarray, protos: np.ndarray, spec: Dict
           ) -> Tuple[np.ndarray, np.ndarray]:
    """Images and labels for the samples whose clients are ``owner``."""
    n = len(owner)
    classes = protos.shape[0]
    dominant = int(spec["dominant_classes"])
    # 70 % of a client's samples come from its `dominant` classes, the rest
    # uniformly from the others; class_order[c] lists client c's classes,
    # dominant ones first
    column = np.where(rng.random(n) < 0.7,
                      rng.integers(0, dominant, n),
                      rng.integers(dominant, classes, n))
    clean = class_order[owner, column].astype(np.int32)
    x = np.empty((n,) + protos.shape[1:], np.float32)
    flat, flat_protos = x.reshape(n, -1), protos.reshape(classes, -1)
    starts = range(0, n, _CHUNK)
    children = rng.spawn(len(starts))

    def fill(i: int) -> None:
        part = flat[starts[i]:starts[i] + _CHUNK]
        children[i].standard_normal(out=part, dtype=np.float32)
        part *= np.float32(spec["pixel_noise"])
        part += flat_protos[clean[starts[i]:starts[i] + _CHUNK]]
        np.clip(part, 0.0, 1.0, out=part)

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, range(len(starts))))
    # label noise: flip to a uniformly random OTHER class with probability
    # 1 - ceiling, so the Bayes-optimal accuracy is the ceiling
    flip = rng.random(n) < 1.0 - float(spec["label_ceiling"])
    offset = rng.integers(1, classes, n)
    y = np.where(flip, (clean + offset) % classes, clean).astype(np.int32)
    return x, y


def build(spec: Dict, clients: int, seed: int):
    """The federation as the program's ``FederatedDataset``, and the
    training rows per client (the benchmark's own count of real rows)."""
    from fedml_tpu.data.base import FederatedDataset

    rng = np.random.default_rng(seed)
    classes, hw, chans = (int(spec["classes"]), int(spec["image_hw"]),
                          int(spec["channels"]))
    n_train, n_test = split_sizes(spec, clients, rng)
    protos = _prototypes(rng, classes, hw, chans)
    class_order = rng.permuted(
        np.tile(np.arange(classes), (clients, 1)), axis=1)
    ids = np.arange(clients)
    xg, yg = _block(rng, np.repeat(ids, n_train), class_order, protos, spec)
    xt, yt = _block(rng, np.repeat(ids, n_test), class_order, protos, spec)
    tr = np.concatenate([[0], np.cumsum(n_train)])
    te = np.concatenate([[0], np.cumsum(n_test)])
    dataset = FederatedDataset(
        client_num=clients,
        train_data_num=len(xg), test_data_num=len(xt),
        train_data_global=(xg, yg), test_data_global=(xt, yt),
        train_data_local_num_dict={c: int(n_train[c])
                                   for c in range(clients)},
        train_data_local_dict={c: (xg[tr[c]:tr[c + 1]], yg[tr[c]:tr[c + 1]])
                               for c in range(clients)},
        test_data_local_dict={c: (xt[te[c]:te[c + 1]], yt[te[c]:te[c + 1]])
                              for c in range(clients)},
        class_num=classes)
    return dataset, n_train
