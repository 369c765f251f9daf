"""Test only: a federation of Gaussian blobs in a flat feature space, every
client the same size - a second generator, found by the name in a
configuration's ``data`` block like the real one."""

import numpy as np


def build(data, clients, seed):
    from fedml_tpu.data.base import FederatedDataset

    rng = np.random.default_rng(seed)
    classes, features = int(data["classes"]), int(data["features"])
    rows, test_rows = int(data["rows"]), int(data["test_rows"])
    centres = rng.normal(size=(classes, features)).astype(np.float32)

    def block(n):
        y = rng.integers(0, classes, n).astype(np.int32)
        x = centres[y] + 0.3 * rng.normal(size=(n, features)).astype(
            np.float32)
        return x, y

    xg, yg = block(clients * rows)
    xt, yt = block(clients * test_rows)
    shard = lambda a, n, c: a[c * n:(c + 1) * n]  # noqa: E731
    dataset = FederatedDataset(
        client_num=clients, train_data_num=len(xg), test_data_num=len(xt),
        train_data_global=(xg, yg), test_data_global=(xt, yt),
        train_data_local_num_dict={c: rows for c in range(clients)},
        train_data_local_dict={c: (shard(xg, rows, c), shard(yg, rows, c))
                               for c in range(clients)},
        test_data_local_dict={c: (shard(xt, test_rows, c),
                                  shard(yt, test_rows, c))
                              for c in range(clients)},
        class_num=classes)
    return dataset, np.full(clients, rows, np.int64)
