"""The road a packed cohort takes from the federation's arrays to the
devices (ISSUE 36): ``pack_clients(out=...)`` into recycled host buffers,
``FedAvgAPI``'s pool of them and its aliasing guard, the mesh's placement of
each device's piece straight from the host, the two counters, and the
benchmark's metric that reads the first.

What must hold: recycling never changes a byte that reaches a device - the
cohort, its order, its padding and its weights are those of a fresh pack -
and a buffer is never rewritten while anything placed shares its memory.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.data import base as data_base
from fedml_tpu.data.base import NATIVE_PACK_FLOOR_BYTES, FederatedDataset
from fedml_tpu.parallel.prefetch import PackBufferPool, aliases_host

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIM = 4096  # a row is 16 KB: eight clients of 64 rows pass the 4 MB floor
SIZES = (64, 23, 57, 31, 64, 40, 17, 52, 28, 61, 35, 46)


def _federation(seed=0, dim=DIM, sizes=SIZES, classes=4):
    rng = np.random.default_rng(seed)
    train = {c: (rng.standard_normal((n, dim)).astype(np.float32),
                 rng.integers(0, classes, n).astype(np.int32))
             for c, n in enumerate(sizes)}
    test = {c: (train[c][0][:2], train[c][1][:2]) for c in train}
    return FederatedDataset.from_client_arrays(train, test, classes)


def _numpy_only(monkeypatch):
    from fedml_tpu import native

    def unavailable(*_a, **_k):
        raise native.NativeUnavailable("test: numpy path")
    monkeypatch.setattr(native, "pack_arrays_native", unavailable)


# -- pack_clients(out=...) ---------------------------------------------------
@pytest.mark.parametrize("path", ["native", "numpy"])
class TestPackInto:
    def test_recycled_triple_equals_a_fresh_pack_byte_for_byte(
            self, path, monkeypatch):
        if path == "numpy":
            _numpy_only(monkeypatch)
        ds = _federation()
        long_first, short_after = [0, 4, 2, 9, 7, 11, 5, 8], \
            [6, 1, 8, 3, 10, 6, 1, 3]
        out = ds.pack_clients(long_first, 16, n_pad=64)
        assert out[0].nbytes >= NATIVE_PACK_FLOOR_BYTES
        if path == "native":
            from fedml_tpu.native import packer_status
            assert packer_status() == "native"
        # the same slots now hold shorter clients: their tails must read 0
        got = ds.pack_clients(short_after, 16, n_pad=64, out=out)
        want = ds.pack_clients(short_after, 16, n_pad=64)
        assert all(g is o for g, o in zip(got, out))
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.tobytes() == w.tobytes()
        x, y, mask = got
        for slot, c in enumerate(short_after):
            n = SIZES[c]
            assert not x[slot, n:].any() and not y[slot, n:].any()
            assert mask[slot, :n].all() and not mask[slot, n:].any()

    def test_buffers_full_of_garbage_are_overwritten(self, path,
                                                     monkeypatch):
        if path == "numpy":
            _numpy_only(monkeypatch)
        ds = _federation()
        cohort = [3, 6, 1, 10, 8, 5, 2, 7]
        want = ds.pack_clients(cohort, 16, n_pad=64)
        out = tuple(np.full_like(a, 7) for a in want)
        got = ds.pack_clients(cohort, 16, n_pad=64, out=out)
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()

    def test_without_out_nothing_changed(self, path, monkeypatch):
        if path == "numpy":
            _numpy_only(monkeypatch)
        ds = _federation()
        a = ds.pack_clients([0, 1, 2, 3, 4, 5, 6, 7], 16, n_pad=64)
        b = ds.pack_clients([0, 1, 2, 3, 4, 5, 6, 7], 16, n_pad=64)
        assert all(p is not q and p.tobytes() == q.tobytes()
                   for p, q in zip(a, b))


@pytest.mark.parametrize("spoil", ["shape", "dtype", "layout", "readonly"])
def test_out_that_does_not_fit_is_refused(spoil):
    ds = _federation(dim=8)
    x, y, mask = ds.pack_clients([0, 1, 2], 16, n_pad=64)
    if spoil == "shape":
        x = np.empty((3, 48, 8), np.float32)
    elif spoil == "dtype":
        y = y.astype(np.int64)
    elif spoil == "layout":
        mask = np.empty((64, 3), np.float32).T
    else:
        x.flags.writeable = False
    with pytest.raises(ValueError, match="out\\["):
        ds.pack_clients([0, 1, 2], 16, n_pad=64, out=(x, y, mask))


def test_population_packs_into_given_buffers():
    from fedml_tpu.state.population import make_virtual_powerlaw_population
    pop = make_virtual_powerlaw_population(client_num=32, dim=6, seed=5,
                                           cache_clients=8)
    want = pop.pack_clients([3, 9, 1], 4)
    out = tuple(np.full_like(a, 9) for a in want)
    got = pop.pack_clients([3, 9, 1], 4, out=out)
    assert all(g is o for g, o in zip(got, out))
    for g, w in zip(got, want):
        assert g.tobytes() == w.tobytes()


# -- the pool ----------------------------------------------------------------
class TestPackBufferPool:
    def test_take_gives_back_what_was_given_for_that_key(self):
        pool, ds = PackBufferPool(3), object()
        assert pool.take(ds, (8, 64)) is None
        a, b = ("a",) * 3, ("b",) * 3
        pool.give(ds, (8, 64), a)
        pool.give(ds, (8, 32), b)
        assert pool.take(ds, (8, 32)) is b
        assert pool.take(ds, (8, 32)) is None
        assert pool.take(ds, (8, 64)) is a and len(pool) == 0

    def test_capacity_drops_the_longest_unused(self):
        pool, ds = PackBufferPool(2), object()
        pool.take(ds, 0)
        for key in (1, 2, 3):
            pool.give(ds, key, (key,) * 3)
        assert len(pool) == 2 and pool.take(ds, 1) is None
        assert pool.take(ds, 3) == (3,) * 3

    def test_another_dataset_drops_everything(self):
        pool, old, new = PackBufferPool(3), object(), object()
        pool.take(old, 1)
        pool.give(old, 1, ("t",) * 3)
        assert pool.take(new, 1) is None and len(pool) == 0
        pool.give(old, 1, ("late",) * 3)  # a pack that raced the swap
        assert len(pool) == 0

    def test_clear_forgets_the_dataset_too(self):
        pool, ds = PackBufferPool(3), object()
        pool.take(ds, 1)
        pool.give(ds, 1, ("t",) * 3)
        pool.clear()
        assert len(pool) == 0
        pool.give(ds, 1, ("in flight at the clear",) * 3)
        assert len(pool) == 0


# -- the aliasing guard --------------------------------------------------------
def _aligned(shape, dtype=np.float32, offset=0):
    """An array whose first byte lies ``offset`` past a 64-byte boundary."""
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.empty(nbytes + 128, np.uint8)
    start = (-raw.ctypes.data) % 64 + offset
    return raw[start:start + nbytes].view(dtype).reshape(shape)


class TestAliasesHost:
    def test_sees_the_cpu_backend_take_an_aligned_buffer_without_copying(
            self):
        host = _aligned((64, 32))
        host[:] = 1.0
        placed = jnp.asarray(host)
        if not _mutated(host, placed):
            pytest.skip("this backend copied an aligned host buffer")
        assert aliases_host(host, placed)

    def test_a_copy_is_not_an_alias(self):
        host = _aligned((64, 32), offset=4)
        host[:] = 1.0
        placed = jnp.asarray(host)
        assert not _mutated(host, placed)
        assert not aliases_host(host, placed)

    def test_every_shard_of_a_sharded_array_is_looked_at(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        sharding = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("c",)),
                                 P("c"))
        host = _aligned((4 * 1024, 64))  # 256 KB a shard, each 64-aligned
        host[:] = 1.0
        placed = jax.device_put(host, sharding)
        shared = _mutated(host, placed)
        assert aliases_host(host, placed) == shared


def _mutated(host, placed) -> bool:
    """Rewrite ``host`` (all ones): did any of ``placed`` change?"""
    jax.block_until_ready(placed)
    host[:] = 2.0
    return bool((np.asarray(placed) != 1.0).any())


# -- the driver: FedAvgAPI._pack_cohort ------------------------------------------
def _config(depth, per_round, rounds=12):
    from fedml_tpu.trainer.functional import TrainConfig
    return dict(comm_round=rounds, client_num_per_round=per_round,
                frequency_of_the_test=10 ** 9, prefetch_depth=depth,
                train=TrainConfig(epochs=1, batch_size=16, lr=0.1))


def _sim_api(ds, depth=2, per_round=8, **kw):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.models.lr import LogisticRegression
    return FedAvgAPI(ds, LogisticRegression(num_classes=4),
                     config=FedAvgConfig(**_config(depth, per_round), **kw))


def _mesh_api(ds, depth=2, per_round=8, n_dev=4):
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                         DistributedFedAvgConfig, build_mesh)
    return DistributedFedAvgAPI(
        ds, LogisticRegression(num_classes=4),
        mesh=build_mesh({"clients": n_dev}, jax.devices()[:n_dev]),
        config=DistributedFedAvgConfig(**_config(depth, per_round)))


def _cohort(api, r):
    from fedml_tpu.core.sampling import sample_clients
    return sample_clients(r, api.dataset.client_num,
                          api.config.client_num_per_round)


@pytest.mark.parametrize("make", [_sim_api, _mesh_api], ids=["sim", "mesh4"])
class TestRecycledCohorts:
    def test_placed_arrays_outlive_the_rounds_that_recycle_their_buffer(
            self, make):
        ds = _federation()
        api = make(ds, depth=0)
        bsz = api.config.train.batch_size
        kept, fresh = [], []
        for r in range(4):
            idxs = _cohort(api, r)
            slots, placed = api._pack_cohort(idxs, ds)
            n_pad = ds.cohort_padded_len(slots, bsz)
            fresh.append(ds.pack_clients(slots, bsz, n_pad=n_pad)
                         + (ds.client_weights(slots),))
            kept.append((slots, placed))
        # rounds 1-3 packed into round 0's triple
        assert api.timer.counters["pack_buffers_fresh"] == 1
        assert api.timer.counters["pack_buffers_recycled"] == 3
        for (slots, placed), want in zip(kept, fresh):
            for got, w in zip(placed, want):
                assert np.asarray(got).tobytes() == np.asarray(
                    jnp.asarray(w)).tobytes()

    def test_an_array_that_a_placed_one_aliases_leaves_the_triple(
            self, make):
        ds = _federation()
        api = make(ds, depth=0)
        idxs = _cohort(api, 0)
        slots, first = api._pack_cohort(idxs, ds)
        want = [np.asarray(a).copy() for a in first[:3]]
        # hand the pool a triple the CPU backend takes without a copy
        key = (len(slots), want[0].shape[1])
        assert api._pack_pool.take(ds, key) is not None
        aligned = tuple(_aligned(a.shape, a.dtype) for a in want)
        api._pack_pool.give(ds, key, aligned)
        _, placed = api._pack_cohort(idxs, ds)
        assert api.timer.counters["pack_buffers_recycled"] == 1
        back = api._pack_pool.take(ds, key)
        if jax.default_backend() == "cpu":  # it shares aligned memory
            assert any(aliases_host(h, p) for h, p in zip(aligned, placed))
        for host, put, kept in zip(aligned, placed, back):
            assert (kept is host) != aliases_host(host, put)
            assert kept.shape == host.shape and kept.dtype == host.dtype
        api._pack_pool.give(ds, key, back)
        for r in (1, 2, 3):
            api._pack_cohort(_cohort(api, r), ds)
        for got, w in zip(placed, want):
            assert np.asarray(got).tobytes() == w.tobytes()

    def test_trajectory_is_the_one_without_a_pool(self, make, monkeypatch):
        ds = _federation()
        piped, plain = make(ds, depth=2), make(ds, depth=2)
        monkeypatch.setattr(plain._pack_pool, "give", lambda *a: None)
        for r in range(6):
            _, a = piped.run_round(r)
            _, b = plain.run_round(r)
            assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
                       for k in a)
        for p, q in zip(jax.tree.leaves(piped.variables),
                        jax.tree.leaves(plain.variables)):
            assert np.array_equal(np.asarray(p), np.asarray(q))
        counts = piped.timer.counters
        assert counts["pack_buffers_recycled"] >= 4
        assert counts["pack_buffers_fresh"] + counts[
            "pack_buffers_recycled"] >= 6
        assert "pack_buffers_recycled" not in plain.timer.counters

    def test_release_prefetch_empties_the_pool(self, make):
        ds = _federation()
        api = make(ds, depth=0)
        api.run_round(0)
        assert len(api._pack_pool) == 1
        api.release_prefetch()
        assert len(api._pack_pool) == 0
        api.run_round(1)  # and the next cohort allocates again
        assert api.timer.counters["pack_buffers_fresh"] == 2

    def test_a_dataset_swap_drops_the_old_dataset_s_triples(self, make):
        ds, other = _federation(0), _federation(1)
        api = make(ds, depth=0)
        api.run_round(0)
        held = api._pack_pool.take(ds, (8, 64))
        api._pack_pool.give(ds, (8, 64), held)
        api.dataset = other
        api.run_round(1)
        assert api.timer.counters["pack_buffers_fresh"] == 2
        assert len(api._pack_pool) == 1
        assert api._pack_pool.take(other, (8, 64))[0] is not held[0]


def test_a_new_padded_length_does_not_get_the_old_length_s_triple():
    ds = _federation()
    api = _sim_api(ds, depth=0)
    longest, short = [0, 4, 9, 2, 7, 11, 5, 8], [6, 1, 8, 3, 6, 1, 8, 3]
    api._pack_cohort(np.array(longest), ds)   # pads to 64
    _, placed = api._pack_cohort(np.array(short), ds)  # to 32: allocates
    assert placed[0].shape[1] == 32
    assert api.timer.counters["pack_buffers_fresh"] == 2
    api._pack_cohort(np.array(short), ds)
    assert api.timer.counters["pack_buffers_recycled"] == 1
    # capacity (depth 0: one triple) keeps the newest length only
    assert api._pack_pool.take(ds, (8, 64)) is None
    assert api._pack_pool.take(ds, (8, 32)) is not None


def test_the_pool_holds_what_the_depth_needs():
    ds = _federation(dim=8)
    assert _sim_api(ds, depth=0)._pack_pool.capacity == 1
    assert _sim_api(ds, depth=2)._pack_pool.capacity == 3


def test_cohorts_under_the_native_floor_keep_the_parent_s_path():
    ds = _federation(dim=8)
    api = _sim_api(ds, depth=0)
    for r in range(3):
        api.run_round(r)
    assert api.timer.counters["pack_buffers_fresh"] == 3
    assert "pack_buffers_recycled" not in api.timer.counters
    assert len(api._pack_pool) == 0


# -- the mesh's placement ----------------------------------------------------------
def test_mesh_put_places_each_device_s_piece_as_the_reshard_did():
    ds = _federation()
    api = _mesh_api(ds, depth=0, per_round=6)  # padded to 8 slots
    idxs = _cohort(api, 0)
    slots, placed = api._pack_cohort(idxs, ds)
    assert len(slots) == 8 and list(slots[:6]) == list(idxs)
    bsz = api.config.train.batch_size
    x, y, mask = ds.pack_clients(slots, bsz,
                                 n_pad=ds.cohort_padded_len(slots, bsz))
    alive = np.array([1] * 6 + [0] * 2, np.float32)
    host = (x, y, mask * alive[:, None], ds.client_weights(slots) * alive)
    devices = list(api.mesh.devices.flat)
    for got, a in zip(placed, host):
        want = jax.device_put(jnp.asarray(a), api._data_sharding)
        assert got.dtype == want.dtype and got.sharding == want.sharding
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
        for k, shard in enumerate(sorted(got.addressable_shards,
                                         key=lambda s: s.index[0].start)):
            assert shard.device == devices[k]
            assert np.array_equal(np.asarray(shard.data),
                                  np.asarray(a[2 * k:2 * k + 2],
                                             dtype=got.dtype))
    # the round's keys are a device array: resharded as before
    keys = api._pack_round(0)[2][3]
    assert keys.sharding == api._data_sharding


# -- the benchmark's metric ----------------------------------------------------------
PACKING_CELLS = ["fedcifar100_resnet18gn.dense",
                 "fedcifar100_resnet18gn.mesh1",
                 "fedcifar100_resnet18gn.mesh4", "femnist_cnn.powerlaw"]


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_pack_recycled_per_round_is_the_last_per_layer_entry(manifest):
    # the last of PR 36's; later PRs append after it
    assert manifest["per_layer"][30:31] == [{
        "name": "pack_recycled_per_round", "unit": "count",
        "better": "higher", "source": "program_counter", "layer": "packer",
        "moves": "rounds_per_s", "workloads": PACKING_CELLS}]
    assert {m["layer"] for m in manifest["per_layer"]
            if m["name"] in ("pack_ms", "produce_ms")} == {"packer"}
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "pack_recycled_per_round.json")) as f:
        entry = json.load(f)
    assert entry["reader"] == "counter_per_round"
    assert entry["args"] == {"counter": "pack_buffers_recycled"}


@pytest.mark.parametrize("cell_name, reports", [
    *[(name, True) for name in PACKING_CELLS],
    ("femnist_cnn.resident", False), ("phi4_mini_flash_6l.silo4", False),
    ("lfm2_8b_a1b_ep4.silo4", False),
    ("granite_4_0_h_micro_10l.silo4", False)])
def test_the_cells_that_pack_a_cohort_a_round_report_it(cell_name, reports):
    from benchmark.harness import spec
    names = [m["name"] for m in spec.load_cell(cell_name).per_layer]
    assert ("pack_recycled_per_round" in names) == reports


@pytest.mark.parametrize("counters, rounds, want", [
    ({"pack_buffers_recycled": 57, "pack_buffers_fresh": 1}, 58, 57 / 58),
    ({"pack_buffers_fresh": 60}, 60, None),  # the parent: no such counter
    ({}, 0, None)])
def test_the_reader_reads_the_counter_and_nothing_where_there_is_none(
        counters, rounds, want):
    import types

    from benchmark.harness import spec
    cell = spec.load_cell("femnist_cnn.powerlaw")
    entry = spec.load_json(cell.find("metrics", "pack_recycled_per_round",
                                     ".json"))
    ctx = types.SimpleNamespace(window=types.SimpleNamespace(
        counters=counters, rounds=rounds))
    got = cell.module("readers", entry["reader"]).read(ctx, **entry["args"])
    assert got == want


def test_pack_buffers_is_exported_for_other_datasets():
    # the virtual population builds its buffers through the same function
    x0, y0 = np.zeros((5, 3), np.float16), np.zeros((5,), np.int64)
    x, y, mask = data_base.pack_buffers(2, 7, x0, y0)
    assert (x.shape, x.dtype) == ((2, 7, 3), np.float16)
    assert (y.shape, y.dtype) == ((2, 7), np.int64)
    assert (mask.shape, mask.dtype) == ((2, 7), np.float32)


def test_a_resident_cohort_keeps_no_host_buffers():
    ds = _federation()
    api = _sim_api(ds, depth=2, per_round=len(SIZES))
    for r in range(3):
        api.run_round(r)
    assert api._pack_cache is not None and len(api._pack_pool) == 0
    assert api.timer.counters["pack_buffers_fresh"] == 1


def test_a_cohort_whose_first_client_has_another_dtype_allocates_afresh():
    # the numpy loop casts such a federation's clients to the first one's
    # dtype (the native packer refuses them): a triple made for float64
    # must not stop the float32-first cohort that follows it
    ds = _federation()
    x0, y0 = ds.train_data_local_dict[0]
    ds.train_data_local_dict[0] = (x0.astype(np.float64), y0)
    api = _sim_api(ds, depth=0)
    _, wide = api._pack_cohort(np.array([0, 4, 9, 2, 7, 11, 5, 8]), ds)
    _, narrow = api._pack_cohort(np.array([4, 0, 9, 2, 7, 11, 5, 8]), ds)
    assert api.timer.counters["pack_buffers_fresh"] == 2
    assert "pack_buffers_recycled" not in api.timer.counters
    want = ds.pack_clients([4, 0, 9, 2, 7, 11, 5, 8], 16, n_pad=64)
    assert np.asarray(narrow[0]).tobytes() == np.asarray(
        jnp.asarray(want[0])).tobytes()
