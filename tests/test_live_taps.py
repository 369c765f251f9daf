"""Padded convolutions that read only the live window of their kernel
(``models/common.py``: ``live_window``, ``LiveTapConv``): the window against
an enumeration of every tap at every output, the convolution and both of its
gradients against ``lax.conv_general_dilated`` with the whole kernel and the
whole padding, and ResNet-18-GN against a copy of itself on plain ``nn.Conv``.

The benchmark's reference shares the module's ``apply`` with the program
(``benchmark/references/local_sgd.py``), so a wrong window would pass the
cells' own check: these tests are the independent ones. Float32 at
``highest`` (``tests/conftest.py``)."""

import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict
from jax import lax

from fedml_tpu.models import create_model, resnet_gn
from fedml_tpu.models.common import LiveTapConv, dead_tap_params, live_window


def _live_taps(size, k, stride, before, after):
    """Every tap that meets a real position at some output, by enumeration."""
    outputs = (size + before + after - k) // stride + 1
    return [t for t in range(k)
            if any(0 <= stride * o + t - before < size
                   for o in range(outputs))]


def _correlate(x, w, stride, before, after):
    """1-D zero-padded correlation in numpy; negative padding crops."""
    x = np.pad(x, (max(before, 0), max(after, 0)))
    x = x[max(-before, 0):len(x) - max(-after, 0)]
    return np.array([x[o:o + len(w)] @ w
                     for o in range(0, len(x) - len(w) + 1, stride)])


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("k", [1, 3, 5, 7])
def test_the_window_is_the_hull_of_the_taps_that_meet_data(k, stride):
    rng = np.random.default_rng(k)
    checked = 0
    for size in range(1, 9):
        for before in range(4):
            for after in range(4):
                pad = (before, after)
                got = live_window(size, k, stride, pad)
                live = _live_taps(size, k, stride, before, after)
                if not live:  # no output, or outputs that see only border
                    assert got == (0, k) + pad
                    continue
                lo, hi, pad_lo, pad_hi = got
                assert (lo, hi) == (live[0], live[-1] + 1), (size, pad)
                x, w = rng.normal(size=size), rng.normal(size=k)
                np.testing.assert_allclose(
                    _correlate(x, w[lo:hi], stride, pad_lo, pad_hi),
                    _correlate(x, w, stride, before, after), atol=1e-12)
                checked += 1
    assert checked >= 80  # of 128: a 7-tap kernel fits few of them


def test_the_issues_windows():
    assert live_window(1, 3, 1, (1, 1)) == (1, 2, 0, 0)  # the centre tap
    assert live_window(2, 3, 2, (1, 1)) == (1, 3, 0, 1)  # four of nine
    assert live_window(2, 3, 1, (1, 1)) == (0, 3, 1, 1)  # none dead
    assert live_window(24, 7, 2, (3, 3)) == (0, 7, 3, 3)
    # taps 0 and 2 of three are live, tap 1 is not: the hull keeps it
    assert live_window(1, 3, 2, (2, 2)) == (0, 3, 2, 2)


def _conv(k, stride, pad):
    return LiveTapConv(5, (k, k), strides=(stride, stride), padding=pad,
                       use_bias=False)


def _full(x, kernel, k, stride, pad):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


CASES = [  # map, kernel, stride, padding, live taps of k * k
    ((1, 1), 3, 1, 1, 1), ((2, 2), 3, 2, 1, 4), ((2, 2), 3, 1, 1, 9),
    ((3, 3), 3, 2, 1, 9), ((24, 24), 7, 2, 3, 49), ((1, 3), 3, 1, 1, 3),
    ((2, 5), 5, 1, 2, 15), ((1, 1), 7, 2, 3, 1)]


@pytest.mark.parametrize("hw, k, stride, pad, live", CASES)
def test_outputs_and_gradients_equal_the_full_kernels(hw, k, stride, pad,
                                                      live):
    x = jax.random.normal(jax.random.key(1), (3,) + hw + (4,))
    conv = _conv(k, stride, pad)
    variables = conv.init(jax.random.key(0), x)
    kernel = variables["params"]["kernel"]
    assert kernel.shape == (k, k, 4, 5)
    ref = nn.Conv(5, (k, k), strides=(stride, stride), padding=pad,
                  use_bias=False).init(jax.random.key(0), x)
    assert np.array_equal(kernel, ref["params"]["kernel"])
    cot = jax.random.normal(jax.random.key(2), conv.apply(variables, x).shape)

    def ours(kernel, x):
        return jnp.sum(conv.apply({"params": {"kernel": kernel}}, x) * cot)

    def full(kernel, x):
        return jnp.sum(_full(x, kernel, k, stride, pad) * cot)

    np.testing.assert_allclose(conv.apply(variables, x),
                               _full(x, kernel, k, stride, pad), atol=1e-6)
    (gk, gx), (rk, rx) = (jax.grad(f, argnums=(0, 1))(kernel, x)
                          for f in (ours, full))
    np.testing.assert_allclose(gx, rx, atol=1e-5)
    np.testing.assert_allclose(gk, rk, atol=1e-5)
    # a tap is dead where the full kernel's gradient is zero for every
    # input: there ours is exactly 0.0, and the count is the window's
    dead = ~np.any(np.asarray(rk) != 0, axis=(2, 3))
    assert k * k - int(dead.sum()) == live
    assert np.all(np.asarray(gk)[dead] == 0.0)
    assert dead_tap_params(
        _Wrap(conv), {"params": {"conv": variables["params"]}},
        x) == (k * k - live) * 20


class _Wrap(nn.Module):
    """A model's signature (``train``) around one convolution."""
    conv: nn.Module

    @nn.compact
    def __call__(self, x, train=False):
        return self.conv(x)


def test_under_vmap_over_four_kernels():
    conv = _conv(3, 2, 1)
    x = jax.random.normal(jax.random.key(1), (4, 2, 2, 2, 4))
    kernels = jax.random.normal(jax.random.key(2), (4, 3, 3, 4, 5))

    def loss(f):
        return lambda kernel, x: jnp.sum(f(kernel, x) ** 2)

    ours = loss(lambda kernel, x: conv.apply(
        {"params": {"kernel": kernel}}, x))
    full = loss(lambda kernel, x: _full(x, kernel, 3, 2, 1))
    got, want = (jax.jit(jax.vmap(jax.value_and_grad(f, argnums=(0, 1))))(
        kernels, x) for f in (ours, full))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    dead = np.ones((3, 3), bool)
    dead[1:, 1:] = False
    assert np.all(np.asarray(got[1][0])[:, dead] == 0.0)
    assert np.all(np.asarray(got[1][0])[:, ~dead] != 0.0)


@pytest.mark.parametrize("hw, k, stride, pad, live", CASES)
def test_where_no_tap_is_dead_the_jaxpr_is_flax_convs(hw, k, stride, pad,
                                                      live):
    x = jnp.zeros((3,) + hw + (4,))
    ours, plain = (str(jax.make_jaxpr(jax.value_and_grad(
        lambda v, x: jnp.sum(cls(
            5, (k, k), strides=(stride, stride), padding=pad,
            use_bias=False).apply(v, x))))(
                {"params": {"kernel": jnp.zeros((k, k, 4, 5))}}, x))
        for cls in (LiveTapConv, nn.Conv))
    assert (ours == plain) == (live == k * k)
    assert ("slice" in ours) == (live != k * k)


@pytest.mark.parametrize("kwargs", [
    dict(use_bias=True), dict(padding="SAME"), dict(kernel_dilation=2),
    dict(feature_group_count=2), dict(padding="CIRCULAR")])
def test_anything_but_a_plain_padded_convolution_is_flax_convs(kwargs):
    kwargs = dict(dict(features=4, kernel_size=(3, 3), padding=1,
                       use_bias=False), **kwargs)
    x = jax.random.normal(jax.random.key(0), (2, 1, 1, 4))
    ours, plain = LiveTapConv(**kwargs), nn.Conv(**kwargs)
    variables = plain.init(jax.random.key(1), x)
    assert (str(jax.make_jaxpr(ours.apply)(variables, x))
            == str(jax.make_jaxpr(plain.apply)(variables, x)))
    assert jax.tree.map(jnp.shape, ours.init(jax.random.key(1), x)) \
        == jax.tree.map(jnp.shape, variables)


# -- ResNet-18-GN ------------------------------------------------------------

def _resnet(small_images):
    return create_model("resnet18_gn", output_dim=100,
                        small_images=small_images)


@pytest.fixture
def plain_resnet(monkeypatch):
    """The model built on plain ``nn.Conv``, as the parent commit has it."""
    def build(small_images):
        monkeypatch.setattr(resnet_gn, "LiveTapConv", nn.Conv)
        return _resnet(small_images)
    yield build


@pytest.mark.parametrize("small_images, params, digest", [
    (False, 11_227_812, "fc88e336629458b7"),
    (True, 11_220_132, "ed891e997345d5ed")])
def test_resnet18_gn_keeps_the_parents_parameter_tree(small_images, params,
                                                      digest):
    """Paths, shapes, dtypes and initial values from ``key(0)``: the digest
    was recorded on commit a211889, before the model knew of live taps."""
    flat = flatten_dict(_resnet(small_images).init(
        jax.random.key(0), jnp.zeros((1, 24, 24, 3)), train=False))
    h = hashlib.sha256()
    for path in sorted(flat):
        a = np.asarray(flat[path])
        h.update(("/".join(path) + str(a.shape) + str(a.dtype)).encode())
        h.update(a.tobytes())
    assert len(flat) == 62 and sum(a.size for a in flat.values()) == params
    assert h.hexdigest()[:16] == digest


def test_resnet18_gn_at_24x24_equals_its_copy_on_plain_convs(plain_resnet):
    """The forward pass and one SGD step of the published model at the
    configuration's crops, where 61.9 % of its parameters are dead taps."""
    x = jax.random.normal(jax.random.key(1), (4, 24, 24, 3))
    y = jnp.arange(4) % 100
    variables = _resnet(False).init(jax.random.key(0), x[:1], train=False)

    def step(module):
        def loss(v):
            logits = module.apply(v, x, train=True)
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), y[:, None], axis=1)), logits

        (value, logits), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(variables)
        return logits, value, grads, jax.tree.map(
            lambda p, g: p - 0.1 * g, variables, grads)

    assert dead_tap_params(_resnet(False), variables, x[:1]) == 6_946_816
    ours = step(_resnet(False))
    plain_module = plain_resnet(False)
    assert dead_tap_params(plain_module, variables, x[:1]) == 0
    plain = step(plain_module)
    scale = float(max(jnp.max(jnp.abs(g)) for g in jax.tree.leaves(plain[2])))
    np.testing.assert_allclose(  # 1e-6 of the largest logit
        ours[0], plain[0], atol=1e-6 * float(jnp.max(jnp.abs(plain[0]))))
    np.testing.assert_allclose(ours[1], plain[1], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(ours[3]), jax.tree.leaves(plain[3])):
        np.testing.assert_allclose(a, b, atol=1e-6 * max(scale, 1.0))
    # 67.7 % of the gradient's entries are exactly zero: the dead taps and
    # nothing the full kernel's gradient does not have at zero as well
    dead = [np.asarray(g) == 0.0 for g in jax.tree.leaves(ours[2])]
    assert sum(int(d.sum()) for d in dead) >= 6_946_816
    kernel = np.asarray(ours[2]["params"]["GNBasicBlock_7"]["Conv_1"]
                        ["kernel"])
    assert np.all(kernel[[0, 0, 0, 1, 1, 2, 2, 2], [0, 1, 2, 0, 2, 0, 1, 2]]
                  == 0.0) and np.any(kernel[1, 1] != 0.0)


@pytest.mark.parametrize("small_images, hw", [(True, 24), (False, 32),
                                              (False, 64)])
def test_resnet18_gn_with_no_dead_tap_traces_the_parents_program(
        plain_resnet, small_images, hw):
    """The 3x3 stem at 24x24 (stages at 24 / 12 / 6 / 3) and the published
    stem at 64x64 (16 / 8 / 4 / 2) keep every tap; at 32x32 the published
    stem ends at 1x1 and must not."""
    x = jnp.zeros((2, hw, hw, 3))
    variables = jax.eval_shape(lambda: _resnet(small_images).init(
        jax.random.key(0), x, train=False))

    def jaxpr(module):
        return str(jax.make_jaxpr(jax.grad(
            lambda v: jnp.sum(module.apply(v, x, train=True))))(variables))

    ours, plain = jaxpr(_resnet(small_images)), jaxpr(
        plain_resnet(small_images))
    assert (ours == plain) == (hw != 32)


# -- the local loop carries the windows alone ---------------------------------

class _TinyNet(nn.Module):
    """A padded 3x3 convolution on the input's map, a stride-2 one on that
    (2x2 -> 1x1), and a head: at 1x1 rows both have dead taps (one tap of
    nine live each), at 2x2 rows only the second has (four of nine)."""

    @nn.compact
    def __call__(self, x, train=False):
        x = nn.relu(LiveTapConv(6, (3, 3), padding=1, use_bias=False)(x))
        x = nn.relu(LiveTapConv(8, (3, 3), strides=(2, 2), padding=1,
                                use_bias=False)(x))
        return nn.Dense(5)(x.reshape(x.shape[0], -1))


LOOPS = {  # TrainConfig fields, on top of epochs=2, batch_size=4, lr=0.1
    "sgd": {}, "momentum": dict(momentum=0.9),
    "amsgrad": dict(client_optimizer="adam", lr=0.01),
    "accum2": dict(accum_steps=2, momentum=0.5),
    "bf16": dict(compute_dtype="bfloat16"), "decay": dict(lr_decay_round=0.5)}


def _local(hw, bounded, **train):
    """A vmapped cohort of three clients (12, 8 and 5 real rows of 12: a
    partial batch and a pure-padding one) through ``make_local_train``."""
    from fedml_tpu.trainer.functional import (TrainConfig, make_local_train,
                                              round_lr_scale)
    module = _TinyNet()
    cfg = TrainConfig(**dict(dict(epochs=2, batch_size=4, lr=0.1), **train))
    x = jax.random.normal(jax.random.key(1), (3, 12, hw, hw, 3))
    y = jax.random.randint(jax.random.key(2), (3, 12), 0, 5)
    mask = (jnp.arange(12)[None, :] < jnp.array([12, 8, 5])[:, None]
            ).astype(jnp.float32)
    variables = module.init(jax.random.key(0), x[0, :1])
    local_train = make_local_train(module, "classification", cfg)

    def cohort(variables, x, y, mask, keys):
        return jax.vmap(lambda xc, yc, mc, kc: local_train(
            variables, xc, yc, mc, kc, lr_scale=round_lr_scale(cfg, 3),
            n_steps=jnp.int32(2) if bounded else None))(x, y, mask, keys)

    return cohort, (variables, x, y, mask,
                    jax.random.split(jax.random.key(3), 3))


@pytest.fixture
def whole_leaves(monkeypatch):
    """The same loop with the model's windows unasked: the parent's."""
    from fedml_tpu.trainer import functional

    def patch():
        monkeypatch.setattr(functional, "live_windows", lambda *a: {})
    yield patch


@pytest.mark.parametrize("bounded", [False, True])
@pytest.mark.parametrize("hw", [1, 2])
@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_the_loop_on_windows_equals_the_loop_on_whole_leaves(
        whole_leaves, loop, hw, bounded):
    cohort, args = _local(hw, bounded, **LOOPS[loop])
    jaxpr = str(jax.make_jaxpr(cohort)(*args))
    (got, got_stats) = jax.jit(cohort)(*args)
    whole_leaves()
    cohort, _ = _local(hw, bounded, **LOOPS[loop])  # traced anew
    assert str(jax.make_jaxpr(cohort)(*args)) != jaxpr
    (want, want_stats) = jax.jit(cohort)(*args)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got_stats), jax.tree.leaves(want_stats)):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    tol = 2e-2 if loop == "bf16" else 1e-6
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol)
    # the dead taps of every client are the global model's, to the bit,
    # on both sides; the live ones have moved
    live = {"LiveTapConv_0": (slice(1, 2),) * 2 if hw == 1 else None,
            "LiveTapConv_1": (slice(1, 2),) * 2 if hw == 1
            else (slice(1, 3),) * 2}
    for name, window in live.items():
        start = np.asarray(args[0]["params"][name]["kernel"])
        for side in (got, want):
            kernels = np.asarray(side["params"][name]["kernel"])
            assert kernels.shape == (3,) + start.shape
            moved = kernels != start[None]
            dead = np.ones(start.shape, bool)
            if window is not None:
                dead[window] = False
                assert not moved[:, dead].any()
            assert moved[:, ~dead if window is not None else dead].any()


@pytest.mark.parametrize("hw", [1, 2])
def test_adam_with_weight_decay_carries_whole_leaves(whole_leaves, hw):
    """``add_decayed_weights`` moves a tap whose gradient is zero: the
    optimizer is asked, and the loop is the whole-leaf one to the jaxpr."""
    from fedml_tpu.trainer.functional import (TrainConfig,
                                              leaves_zero_gradients_alone)
    cohort, args = _local(hw, False, client_optimizer="adam", wd=0.01)
    ours = str(jax.make_jaxpr(cohort)(*args))
    start = np.asarray(args[0]["params"]["LiveTapConv_1"]["kernel"])
    moved = np.asarray(jax.jit(cohort)(*args)[0]["params"]["LiveTapConv_1"]
                       ["kernel"]) != start[None]
    assert moved[:, 0, 0].any()  # a dead tap, decayed
    whole_leaves()
    cohort, _ = _local(hw, False, client_optimizer="adam", wd=0.01)
    assert str(jax.make_jaxpr(cohort)(*args)) == ours
    for train, alone in [
            (dict(), True), (dict(momentum=0.9), True),
            (dict(client_optimizer="adam"), True),
            (dict(client_optimizer="adam", accum_steps=3), True),
            (dict(momentum=0.9, accum_steps=2), True),
            (dict(client_optimizer="adam", wd=1e-4), False),
            (dict(client_optimizer="adam", wd=1e-4, accum_steps=2), False)]:
        assert leaves_zero_gradients_alone(TrainConfig(**train)) is alone, \
            train


@pytest.mark.parametrize("bounded", [False, True])
def test_a_model_without_windows_traces_the_parents_loop(bounded):
    """A vmapped cohort of the CNN through ``make_local_train``: the digest
    was recorded on commit cb91b64, before the loop knew of windows."""
    from fedml_tpu.trainer.functional import TrainConfig, make_local_train
    module = create_model("cnn", output_dim=62)
    local_train = make_local_train(
        module, "classification",
        TrainConfig(epochs=1, batch_size=4, lr=0.1, momentum=0.9))
    variables = module.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)),
                            train=False)
    text = str(jax.make_jaxpr(jax.vmap(
        lambda v, xc, yc, mc, kc: local_train(
            v, xc, yc, mc, kc, n_steps=jnp.int32(1) if bounded else None),
        in_axes=(None, 0, 0, 0, 0)))(
            variables, jnp.zeros((2, 8, 28, 28, 1)),
            jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8)),
            jax.random.split(jax.random.key(0), 2)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == {
        False: "bf3bd686755d1aae", True: "d849df22a4bd28b3"}[bounded]


@pytest.mark.parametrize("kernel, accepted", [
    ((3, 3, 4, 5), True), ((1, 1, 4, 5), True), ((2, 2, 4, 5), False),
    ((1, 1, 4, 6), False)])
def test_a_stored_kernel_is_the_declared_one_or_its_window(kernel, accepted):
    conv, x = _conv(3, 1, 1), jnp.ones((2, 1, 1, 4))
    variables = {"params": {"kernel": jnp.ones(kernel)}}
    if not accepted:
        with pytest.raises(Exception, match="shape"):
            conv.apply(variables, x)
        return
    np.testing.assert_allclose(conv.apply(variables, x), 4.0)
    text = str(jax.make_jaxpr(conv.apply)(variables, x))
    assert ("optimization_barrier" in text) == (kernel[0] == 3)
    assert ("slice" in text) == (kernel[0] == 3)


def test_live_windows_of_the_published_resnet():
    from fedml_tpu.models.common import live_windows, window_shape
    module = _resnet(False)
    x = jnp.zeros((1, 24, 24, 3))
    variables = jax.eval_shape(lambda: module.init(jax.random.key(0), x,
                                                   train=False))
    windows = live_windows(module, variables, x)
    assert windows == {
        ("GNBasicBlock_6", "Conv_0", "kernel"): ((1, 3), (1, 3)),
        ("GNBasicBlock_6", "Conv_1", "kernel"): ((1, 2), (1, 2)),
        ("GNBasicBlock_7", "Conv_0", "kernel"): ((1, 2), (1, 2)),
        ("GNBasicBlock_7", "Conv_1", "kernel"): ((1, 2), (1, 2))}
    flat = flatten_dict(variables["params"])
    carried = sum(int(np.prod(window_shape(windows[p], a.shape)))
                  if p in windows else a.size for p, a in flat.items())
    assert carried == 4_280_996 == 11_227_812 - 6_946_816
    assert live_windows(module, variables, jnp.zeros((1, 64, 64, 3))) == {}
    assert live_windows(_resnet(True), jax.eval_shape(
        lambda: _resnet(True).init(jax.random.key(0), x, train=False)),
        x) == {}


@pytest.mark.parametrize("driver, train, carried", [
    ("sim", dict(), 399), ("mesh", dict(), 399),
    ("sim", dict(client_optimizer="adam", wd=0.01), 639)])
def test_both_drivers_count_and_train_what_the_loop_carries(
        whole_leaves, driver, train, carried):
    """Two rounds of either driver over 2x2 rows (the first convolution has
    no dead tap, the second's window is four of nine): the model the
    windows give is the one whole leaves give, the dead taps of the global
    model stay where they were under SGD, and ``local_carried_params`` says which
    loop ran: of 162 + 432 + 45 parameters, 240 are dead taps."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.data.base import FederatedDataset
    from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                         DistributedFedAvgConfig, build_mesh)
    from fedml_tpu.trainer.functional import TrainConfig

    rng = np.random.default_rng(0)
    clients = {c: (rng.normal(size=(6, 2, 2, 3)).astype(np.float32),
                   rng.integers(0, 5, 6).astype(np.int32)) for c in range(4)}
    ds = FederatedDataset.from_client_arrays(clients, clients, class_num=5)
    kw = dict(comm_round=2, client_num_per_round=4, prefetch_depth=0,
              train=TrainConfig(**dict(dict(epochs=1, batch_size=3, lr=0.1),
                                       **train)))

    def run():
        if driver == "sim":
            api = FedAvgAPI(ds, _TinyNet(), config=FedAvgConfig(**kw))
        else:
            api = DistributedFedAvgAPI(
                ds, _TinyNet(), mesh=build_mesh({"clients": 2}),
                config=DistributedFedAvgConfig(**kw))
        start = jax.tree.map(np.asarray, api.variables)
        for r in range(2):
            api.run_round(r)
        return api, start

    api, start = run()
    counters = api.timer.counters
    assert counters["conv_dead_tap_params"] == 240
    assert counters["local_carried_params"] == carried
    whole_leaves()
    want, _ = run()
    assert want.timer.counters["local_carried_params"] == 639
    for a, b in zip(jax.tree.leaves(api.variables),
                    jax.tree.leaves(want.variables)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # a client returns the global model's dead taps to the bit (the tests
    # above); the mean of equal values rounds them, as it always has
    kernel = np.asarray(api.variables["params"]["LiveTapConv_1"]["kernel"])
    moved = ~np.isclose(kernel, start["params"]["LiveTapConv_1"]["kernel"],
                        rtol=1e-6, atol=0)
    assert moved[1:, 1:].any()
    assert moved[0].any() == moved[:, 0].any() == bool(train)
