"""The federated dataset contract and its device-ready array packing.

The reference's framework-wide ABI is a 9-tuple every loader returns:
``client_num, train_data_num, test_data_num, train_data_global,
test_data_global, train_data_local_num_dict, train_data_local_dict,
test_data_local_dict, class_num`` (e.g.
fedml_api/data_preprocessing/FederatedEMNIST/data_loader.py:149-150, consumed
at fedml_experiments/distributed/fedavg/main_fedavg.py:120-227). We keep that
contract but hold **numpy arrays**, not torch DataLoaders, and add the one
operation the TPU path needs: ``pack_clients`` — gather a set of sampled
clients into rectangular padded-and-masked arrays whose leading axis is the
client/mesh axis. Ragged LEAF-style client sizes become a static shape
(max client size rounded to a batch multiple) + a 0/1 mask, which is what lets
the whole round run as one compiled SPMD program (SURVEY §7 "pad-and-mask").
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

Arrays = Tuple[np.ndarray, np.ndarray]  # (x, y)

#: a packed ``x`` of at least this many bytes goes through the native
#: packer (``fedml_tpu/native``), and its buffers are worth recycling
#: (``FedAvgAPI._pack_cohort``); smaller cohorts take the numpy loop
NATIVE_PACK_FLOOR_BYTES = 1 << 22


def pack_buffers(P: int, n_pad: int, x0: np.ndarray, y0: np.ndarray,
                 out=None, alloc=np.empty):
    """The ``(x, y, mask)`` a cohort of ``P`` clients like ``(x0, y0)`` is
    packed into: fresh arrays from ``alloc`` (uninitialised by default),
    or the caller's ``out`` when it is exactly what would be allocated
    (else ``ValueError``)."""
    want = (((P, n_pad) + x0.shape[1:], x0.dtype),
            ((P, n_pad) + y0.shape[1:], y0.dtype),
            ((P, n_pad), np.dtype(np.float32)))
    if out is None:
        return tuple(alloc(shape, dtype) for shape, dtype in want)
    for name, a, (shape, dtype) in zip(("x", "y", "mask"), out, want):
        if (a.shape != shape or a.dtype != dtype
                or not a.flags.c_contiguous or not a.flags.writeable):
            raise ValueError(
                f"out[{name}] must be a writeable C-contiguous "
                f"{dtype}{shape}; got {a.dtype}{a.shape}")
    return tuple(out)


@dataclasses.dataclass
class FederatedDataset:
    client_num: int
    train_data_num: int
    test_data_num: int
    train_data_global: Arrays
    test_data_global: Arrays
    train_data_local_num_dict: Dict[int, int]
    train_data_local_dict: Dict[int, Arrays]
    test_data_local_dict: Dict[int, Optional[Arrays]]
    class_num: int

    @classmethod
    def from_client_arrays(cls, train_local: Dict[int, Arrays],
                           test_local: Dict[int, Optional[Arrays]],
                           class_num: int) -> "FederatedDataset":
        clients = sorted(train_local)
        xg = np.concatenate([train_local[c][0] for c in clients])
        yg = np.concatenate([train_local[c][1] for c in clients])
        tests = [test_local.get(c) for c in clients]
        tests = [t for t in tests if t is not None and len(t[0])]
        xt = np.concatenate([t[0] for t in tests]) if tests else xg[:0]
        yt = np.concatenate([t[1] for t in tests]) if tests else yg[:0]
        return cls(
            client_num=len(clients),
            train_data_num=len(xg),
            test_data_num=len(xt),
            train_data_global=(xg, yg),
            test_data_global=(xt, yt),
            train_data_local_num_dict={c: len(train_local[c][0]) for c in clients},
            train_data_local_dict=train_local,
            test_data_local_dict=test_local,
            class_num=class_num,
        )

    def as_tuple(self):
        """The reference 9-tuple, verbatim order."""
        return (self.client_num, self.train_data_num, self.test_data_num,
                self.train_data_global, self.test_data_global,
                self.train_data_local_num_dict, self.train_data_local_dict,
                self.test_data_local_dict, self.class_num)

    # -- TPU packing -------------------------------------------------------
    @property
    def max_client_samples(self) -> int:
        return max(self.train_data_local_num_dict.values())

    def padded_len(self, batch_size: Optional[int]) -> int:
        """Static per-client length: max client size rounded up to a batch
        multiple (full batch => exactly the max size)."""
        n = self.max_client_samples
        if not batch_size:
            return n
        return ((n + batch_size - 1) // batch_size) * batch_size

    def cohort_padded_len(self, client_idxs,
                          batch_size: Optional[int]) -> int:
        """Cohort-shaped padded length: the *sampled cohort's* max client
        size rounded to a batch multiple, then snapped UP to a power-of-2
        batch count so the number of distinct compiled round shapes stays
        O(log2(max batches)), capped at the dataset-wide ``padded_len``.

        On power-law federations (reference MNIST: max client ≫ median,
        fedml_api/data_preprocessing/MNIST/data_loader.py:88) padding every
        sampled client to the dataset-wide max makes masked padding rows the
        majority of per-round FLOPs; padding to the cohort's bucket removes
        that waste while the pow-2 snap bounds recompiles."""
        n = max(self.train_data_local_num_dict[int(c)] for c in client_idxs)
        b = batch_size or 1
        nb = (n + b - 1) // b
        bucket = 1 << max(0, (nb - 1).bit_length())
        return min(bucket * b, self.padded_len(batch_size))

    def pack_clients(self, client_idxs, batch_size: Optional[int] = None,
                     n_pad: Optional[int] = None, out=None):
        """Gather sampled clients into [P, n_pad, ...] x / [P, n_pad, ...] y /
        [P, n_pad] mask arrays — the device-ready round input. ``n_pad``
        defaults to the dataset-wide static shape so every round compiles
        once.

        ``out=(x, y, mask)``: write into these arrays and return them
        instead of allocating (C-contiguous, of exactly the shapes and
        dtypes this call would allocate, else ``ValueError``). Every byte
        of them is written - real rows, zeroed tails, the mask - so what
        they held before does not matter. They are the caller's: the
        packer keeps no reference, and the caller alone knows when a
        buffer is free to be written again (``FedAvgAPI`` recycles a
        cohort's triple once its upload is over, ``_pack_cohort``). A
        fresh array costs a page fault and a zeroed page for every 4 KB
        written, two thirds of a large pack; a buffer that is reused has
        its pages already."""
        n_pad = n_pad or self.padded_len(batch_size)
        x0, y0 = self.train_data_local_dict[int(client_idxs[0])]
        P = len(client_idxs)
        x, y, mask = pack_buffers(P, n_pad, x0, y0, out)
        xs = [self.train_data_local_dict[int(c)][0] for c in client_idxs]
        ys = [self.train_data_local_dict[int(c)][1] for c in client_idxs]
        for c, cx, cy in zip(client_idxs, xs, ys):
            if len(cx) > n_pad:
                raise ValueError(
                    f"client {c} has {len(cx)} samples > n_pad={n_pad}")
            if len(cx) != len(cy):
                raise ValueError(
                    f"client {c}: {len(cx)} samples but {len(cy)} labels")
        # the native packer copies clients in parallel (a thread per 16 MB
        # of destination, at most 8 and the cores); on single-core hosts it
        # matches the numpy loop exactly (both are one memcpy per client),
        # so dispatch costs nothing and multi-core TPU hosts get the
        # bandwidth win. Small cohorts (or no toolchain / exotic per-client
        # layouts) take the numpy loop.
        if x.nbytes >= NATIVE_PACK_FLOOR_BYTES:
            try:
                from fedml_tpu.native import (NativeUnavailable,
                                              pack_arrays_native)
                pack_arrays_native(xs, x, mask)
                pack_arrays_native(ys, y)
                return x, y, mask
            except (NativeUnavailable, ValueError):
                pass  # numpy loop below casts/raises with full context
        for i in range(P):
            n = len(xs[i])
            x[i, :n], x[i, n:] = xs[i], 0
            y[i, :n], y[i, n:] = ys[i], 0
            mask[i, :n], mask[i, n:] = 1.0, 0.0
        return x, y, mask

    def client_weights(self, client_idxs) -> np.ndarray:
        """Sample counts n_i for the weighted FedAvg average."""
        return np.array(
            [self.train_data_local_num_dict[int(c)] for c in client_idxs],
            dtype=np.float32)
