"""``ops/moe.py``: top-k routing over all the experts, the grouped products
over the experts held, no drops at any load, static shapes - against a
masked-dense sum over the experts (every expert on every token), at small
sizes on the CPU, float32 at ``highest``."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import moe
from fedml_tpu.ops.moe import route, routed_experts

T, D, W, E, K = 48, 32, 40, 8, 2


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(seed=0, experts=E, bias_scale=0.1):
    ks = jax.random.split(jax.random.key(seed), 6)
    return {"s": jax.random.normal(ks[0], (T, D)),
            "router": 0.3 * jax.random.normal(ks[1], (D, experts)),
            "bias": bias_scale * jax.random.normal(ks[2], (experts,)),
            "w1": 0.2 * jax.random.normal(ks[3], (experts, D, W)),
            "w3": 0.2 * jax.random.normal(ks[4], (experts, D, W)),
            "w2": 0.2 * jax.random.normal(ks[5], (experts, W, D))}


def _share(p, first, count, block=moe.BLOCK):
    """The program's layer on the share ``first .. first + count - 1``, in
    blocks of ``block`` rows (the layer reads ``BLOCK`` as it is traced), so
    that 48 tokens fill several blocks an expert."""
    held = slice(first, first + count)
    with mock.patch.object(moe, "BLOCK", block):
        return routed_experts(p["s"], p["router"], p["bias"], p["w1"][held],
                              p["w3"][held], p["w2"][held], top_k=K,
                              experts_held=(first, count))


def _dense(p, first, count, top_k=K):
    """Every held expert on every token, weighted by the routing weight."""
    prob = jax.nn.sigmoid(p["s"] @ p["router"])
    _, chosen = jax.lax.top_k(prob + p["bias"], top_k)
    picked = jnp.take_along_axis(prob, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    out = jnp.zeros_like(p["s"])
    for e in range(first, first + count):
        w_e = jnp.where(chosen == e, weights, 0.0).sum(-1)
        out = out + w_e[:, None] * ((jax.nn.silu(p["s"] @ p["w1"][e])
                                     * (p["s"] @ p["w3"][e])) @ p["w2"][e])
    return out, chosen


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))


@pytest.mark.parametrize("first, count", [(0, 8), (2, 4), (6, 2), (3, 1)])
@pytest.mark.parametrize("block", [8, 16, 512])
def test_a_share_equals_the_masked_dense_sum(first, count, block):
    p = _weights()
    y, load = jax.jit(lambda p: _share(p, first, count, block=block))(p)
    want, chosen = _dense(p, first, count)
    assert y.shape == want.shape and _rel(y, want) < 1e-5
    np.testing.assert_array_equal(load, [
        int(jnp.sum(chosen == e)) for e in range(first, first + count)])


def test_the_four_shares_add_up_to_the_uncut_block():
    """The tie between the share and the model: router, bias and the
    normalisation over all the chosen are every share's alike and counted
    once; the parts the four chips compute sum to the whole block."""
    p = _weights(seed=3)
    parts = [jax.jit(lambda p, f=f: _share(p, f, 2, block=8))(p)
             for f in (0, 2, 4, 6)]
    whole, chosen = _dense(p, 0, E)
    assert _rel(sum(y for y, _ in parts), whole) < 1e-5
    loads = np.concatenate([load for _, load in parts])
    assert loads.sum() == T * K  # every pair lands on exactly one chip
    np.testing.assert_array_equal(loads, np.bincount(
        np.asarray(chosen).ravel(), minlength=E))


@pytest.mark.parametrize("first, count, pairs", [(0, 2, T * K), (4, 3, 0)])
def test_no_pair_is_dropped_when_all_or_none_land_here(first, count, pairs):
    """The static worst case (every token's every choice on a held expert)
    and the empty one, through the same compiled shapes."""
    p = _weights(seed=1)
    p["bias"] = p["bias"].at[:2].add(10.0)  # everybody chooses 0 and 1
    y, load = jax.jit(lambda p: _share(p, first, count, block=16))(p)
    want, chosen = _dense(p, first, count)
    assert set(np.asarray(chosen).ravel().tolist()) == {0, 1}
    assert int(load.sum()) == pairs
    assert _rel(y, want) < 1e-5 if pairs else not np.any(np.asarray(y))


def test_the_bias_selects_and_the_weights_are_the_unbiased_scores():
    p = _weights(seed=2, bias_scale=0.0)
    p["bias"] = jnp.zeros(E).at[5].set(5.0)  # expert 5 wins everywhere
    chosen, weights = route(p["s"], p["router"], p["bias"], top_k=K,
                            norm_topk=True, scale=1.0)
    plain, _ = route(p["s"], p["router"], None, top_k=K, norm_topk=True,
                     scale=1.0)
    assert np.all(np.any(np.asarray(chosen) == 5, axis=-1))
    assert np.any(np.sort(chosen, -1) != np.sort(plain, -1))
    prob = np.asarray(jax.nn.sigmoid(p["s"] @ p["router"]))
    picked = np.take_along_axis(prob, np.asarray(chosen), axis=-1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert float(weights.max()) < 1.0  # never the biased score (5 + p)
    # no gradient reaches the bias
    grad = jax.grad(lambda b: jnp.sum(_share({**p, "bias": b}, 0, E)[0]))(
        p["bias"])
    assert not np.any(np.asarray(grad))


def test_weights_without_normalisation_are_scaled_scores():
    p = _weights(seed=4)
    chosen, weights = route(p["s"], p["router"], p["bias"], top_k=3,
                            norm_topk=False, scale=2.5)
    prob = np.asarray(jax.nn.sigmoid(p["s"] @ p["router"]))
    np.testing.assert_allclose(weights, 2.5 * np.take_along_axis(
        prob, np.asarray(chosen), axis=-1), rtol=1e-6)


@pytest.mark.parametrize("first, count", [(0, 8), (2, 4)])
@pytest.mark.parametrize("block", [8, 16])
def test_every_gradient_equals_the_dense_sums(first, count, block):
    p = _weights(seed=5)
    probe = jax.random.normal(jax.random.key(9), (T, D))

    def through(fn):
        return jax.jit(jax.grad(lambda p: jnp.sum(fn(p) * probe)))(p)

    got = through(lambda p: _share(p, first, count, block=block)[0])
    want = through(lambda p: _dense(p, first, count)[0])
    for name in ("s", "router", "w1", "w3", "w2"):
        assert _rel(got[name], want[name]) < 1e-5, name


def test_the_backward_pass_under_checkpoint_inside_a_scan():
    """As the round runs it: the layer rematerialised, inside the scan over
    local steps."""
    p = _weights(seed=6)

    def loss(p, fn):
        def step(s, _):
            out = fn({**p, "s": s})
            return s + out, jnp.sum(out)
        s, sums = jax.lax.scan(step, p["s"], None, length=2)
        return jnp.sum(s ** 2) + jnp.sum(sums)

    got = jax.jit(jax.grad(lambda p: loss(p, jax.checkpoint(
        lambda p: _share(p, 2, 4, block=8)[0]))))(p)
    want = jax.jit(jax.grad(lambda p: loss(
        p, lambda p: _dense(p, 2, 4)[0])))(p)
    for name in ("s", "router", "w1", "w3", "w2"):
        assert _rel(got[name], want[name]) < 1e-5, name


def test_shapes_do_not_depend_on_the_routing():
    fn = jax.jit(lambda p: _share(p, 0, 4, block=8))
    for seed in range(3):
        fn(_weights(seed=seed))
    p = _weights(seed=0)
    fn({**p, "bias": p["bias"].at[:2].add(10.0)})   # all on held experts
    fn({**p, "bias": p["bias"].at[6:].add(10.0)})   # none
    assert fn._cache_size() == 1


def test_rows_lead_the_load_and_share_the_products():
    """``s [B, T, d]``: one set of grouped products over all the rows' tokens,
    the load counted row by row."""
    p = _weights(seed=7)
    rows = jnp.stack([p["s"], p["s"][::-1]])
    y, load = jax.jit(lambda s: _share({**p, "s": s}, 1, 5, block=16))(rows)
    one, load_one = _share(p, 1, 5, block=16)
    assert y.shape == (2, T, D) and load.shape == (2, 5)
    assert _rel(y[0], one) < 1e-5 and _rel(y[1][::-1], one) < 1e-5
    np.testing.assert_array_equal(load[0], load_one)
    np.testing.assert_array_equal(load[1], load_one)


def test_under_vmap_every_lane_is_exact():
    p = _weights(seed=8)
    inputs = jnp.stack([p["s"], 2.0 * p["s"], -p["s"]])
    got, loads = jax.jit(jax.vmap(
        lambda s: _share({**p, "s": s}, 0, 4, block=8)))(inputs)
    for i in range(3):
        want, chosen = _dense({**p, "s": inputs[i]}, 0, 4)
        assert _rel(got[i], want) < 1e-5
        assert int(loads[i].sum()) == int(jnp.sum(chosen < 4))


def test_a_wrong_expert_count_is_refused():
    p = _weights()
    with pytest.raises(ValueError, match="experts_held says"):
        routed_experts(p["s"], p["router"], p["bias"], p["w1"][:3],
                       p["w3"][:3], p["w2"][:3], top_k=K,
                       experts_held=(0, 4))


# -- small loads in large blocks; the epsilon is the caller's (PR 39) --------------

@pytest.mark.parametrize("first, count", [(0, 8), (2, 4)])
def test_blocks_of_8_and_of_512_give_the_same_values_and_gradients(first,
                                                                   count):
    """96 pairs fill a dozen blocks of 8 and a part of one of 512: the
    regime of many small experts (a few rows in a block of ``BLOCK``)."""
    p = _weights(seed=10)
    probe = jax.random.normal(jax.random.key(11), (T, D))

    def through(block):
        def loss(p):
            y, load = _share(p, first, count, block=block)
            return jnp.sum(y * probe), (y, load)
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(p)

    (_, (small, load_small)), grad_small = through(8)
    (_, (large, load_large)), grad_large = through(512)
    want, _ = _dense(p, first, count)
    assert _rel(small, want) < 1e-5 and _rel(large, want) < 1e-5
    np.testing.assert_array_equal(load_small, load_large)
    for name in ("s", "router", "w1", "w3", "w2"):
        assert _rel(grad_small[name], grad_large[name]) < 1e-5, name
    assert not np.any(np.asarray(grad_small["bias"]))


@pytest.mark.parametrize("block", [8, 128])
def test_a_load_under_one_block_and_one_of_several(block):
    """Expert 0 is everybody's choice (48 pairs: six blocks of 8, a part of
    one of 128), expert 1 is chosen where its score wins the second place
    (a few pairs: under one block of either size)."""
    p = _weights(seed=12, bias_scale=0.0)
    p["bias"] = jnp.zeros(E).at[0].set(10.0)
    y, load = jax.jit(lambda p: _share(p, 0, 2, block=block))(p)
    want, chosen = _dense(p, 0, 2)
    assert int(load[0]) == T and 0 < int(load[1]) < 8
    assert int(load[1]) == int(jnp.sum(chosen == 1))
    assert _rel(y, want) < 1e-5


def test_the_blocks_the_plan_runs_follow_the_block():
    chosen = jnp.asarray(np.random.RandomState(0).randint(0, E, (T, K)),
                         jnp.int32)
    load = np.bincount(np.asarray(chosen).ravel(), minlength=E)[2:6]
    for block in (8, 16, 512):
        with mock.patch.object(moe, "BLOCK", block):
            plan = moe._plan(chosen, 2, 4)
        size = min(block, T * K)
        assert plan.tokens.shape[1] == size
        assert int(plan.n_run) == int(np.sum(-(-load // size)))
        assert int(plan.valid.sum()) == int(load.sum())


def test_the_epsilon_of_the_normalisation_is_the_callers():
    p = _weights(seed=13)
    prob = np.asarray(jax.nn.sigmoid(p["s"] @ p["router"]), np.float64)
    for eps in (1e-20, 1e-6, 0.5):
        chosen, weights = route(p["s"], p["router"], p["bias"], top_k=K,
                                norm_topk=True, scale=2.448, eps=eps)
        picked = np.take_along_axis(prob, np.asarray(chosen), axis=-1)
        np.testing.assert_allclose(
            weights, 2.448 * picked / (picked.sum(-1, keepdims=True) + eps),
            rtol=1e-5)
    # left out it is 1e-6, and ``routed_experts`` hands it on
    _, default = route(p["s"], p["router"], p["bias"], top_k=K,
                       norm_topk=True, scale=1.0)
    _, named = route(p["s"], p["router"], p["bias"], top_k=K,
                     norm_topk=True, scale=1.0, eps=1e-6)
    np.testing.assert_array_equal(default, named)
    def whole(eps):
        return routed_experts(p["s"], p["router"], p["bias"], p["w1"],
                              p["w3"], p["w2"], top_k=K,
                              experts_held=(0, E), eps=eps)[0]

    loose, tight = whole(0.5), whole(1e-20)
    assert _rel(loose, tight) > 0.1


#: the first 16 hex digits of sha256(str(jaxpr)) of the layer and of its
#: five gradients at commit 6105f72 (PR 38, before ``eps`` was a keyword),
#: for a call shaped like ``models/lfm2_moe.py``'s: rows of tokens, top-4 of
#: 32 experts, 8 held, no epsilon named
LFM2_SHAPED = {"layer": "f2d848aaa50d8aa0", "gradients": "c2df61f56eb10cf9"}


@pytest.mark.parametrize("what", sorted(LFM2_SHAPED))
def test_with_the_defaults_the_traced_program_is_the_parents(what):
    import hashlib

    def layer(s, router, bias, w1, w3, w2):
        return routed_experts(s, router, bias, w1, w3, w2, top_k=4,
                              experts_held=(0, 8), norm_topk=True, scale=1.0)

    def gradients(*args):
        return jax.grad(lambda *a: jnp.sum(layer(*a)[0] ** 2),
                        argnums=(0, 1, 3, 4, 5))(*args)

    args = [jax.ShapeDtypeStruct(shape, jnp.float32) for shape in (
        (2, 64, 32), (32, 32), (32,), (8, 32, 48), (8, 32, 48), (8, 48, 32))]
    fn = {"layer": layer, "gradients": gradients}[what]
    with jax.default_matmul_precision(None):
        got = hashlib.sha256(
            str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()[:16]
    assert got == LFM2_SHAPED[what]


# -- a softmax router beside the sigmoid one ----------------------------------------

def _dense_softmax(p, first, count, top_k=K):
    """Every held expert on every token, weighted by the softmax over all
    the experts renormalised over the chosen (no bias, no epsilon)."""
    prob = jax.nn.softmax(p["s"] @ p["router"], axis=-1)
    picked, chosen = jax.lax.top_k(prob, top_k)
    weights = picked / picked.sum(-1, keepdims=True)
    out = jnp.zeros_like(p["s"])
    for e in range(first, first + count):
        w_e = jnp.where(chosen == e, weights, 0.0).sum(-1)
        out = out + w_e[:, None] * ((jax.nn.silu(p["s"] @ p["w1"][e])
                                     * (p["s"] @ p["w3"][e])) @ p["w2"][e])
    return out, chosen


@pytest.mark.parametrize("top_k", [2, 3])
def test_softmax_scores_choose_the_top_k_of_the_softmax(top_k):
    p = _weights(seed=14)
    chosen, weights = route(p["s"], p["router"], None, top_k=top_k,
                            norm_topk=True, scale=1.0, eps=0.0,
                            score="softmax")
    prob = np.asarray(jax.nn.softmax(p["s"] @ p["router"], axis=-1))
    plain = np.argsort(-prob, axis=-1, kind="stable")[:, :top_k]
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(plain, -1))
    picked = np.take_along_axis(prob, np.asarray(chosen), axis=-1)
    np.testing.assert_allclose(
        weights, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # unnormalised, the weights are the softmax over all the experts
    _, raw = route(p["s"], p["router"], None, top_k=top_k, norm_topk=False,
                   scale=1.0, score="softmax")
    np.testing.assert_allclose(raw, picked, rtol=1e-6)
    assert float(np.max(np.sum(raw, -1))) < 1.0


@pytest.mark.parametrize("first, count", [(0, 8), (2, 4)])
def test_a_softmax_share_and_its_gradients_equal_the_masked_dense_sum(
        first, count):
    p = _weights(seed=15)
    probe = jax.random.normal(jax.random.key(16), (T, D))

    def share(p):
        held = slice(first, first + count)
        with mock.patch.object(moe, "BLOCK", 8):
            return routed_experts(
                p["s"], p["router"], None, p["w1"][held], p["w3"][held],
                p["w2"][held], top_k=K, experts_held=(first, count),
                eps=0.0, score="softmax")

    y, load = jax.jit(share)(p)
    want, chosen = _dense_softmax(p, first, count)
    assert _rel(y, want) < 1e-5
    np.testing.assert_array_equal(load, [
        int(jnp.sum(chosen == e)) for e in range(first, first + count)])
    got = jax.jit(jax.grad(lambda p: jnp.sum(share(p)[0] * probe)))(p)
    ref = jax.jit(jax.grad(lambda p: jnp.sum(
        _dense_softmax(p, first, count)[0] * probe)))(p)
    for name in ("s", "router", "w1", "w3", "w2"):
        assert _rel(got[name], ref[name]) < 1e-5, name
    # not the sigmoid's weights
    sigmoid, _ = _dense(p, first, count)
    assert _rel(sigmoid, want) > 1e-3


def test_the_sigmoid_is_the_default_and_another_score_is_refused():
    p = _weights(seed=17)
    args = (p["s"], p["router"], p["bias"])
    kw = dict(top_k=K, norm_topk=True, scale=1.0)
    default, named = route(*args, **kw), route(*args, **kw, score="sigmoid")
    for a, b in zip(default, named):
        np.testing.assert_array_equal(a, b)
    assert str(jax.make_jaxpr(lambda *a: route(*a, **kw))(*args)) == str(
        jax.make_jaxpr(lambda *a: route(*a, **kw, score="sigmoid"))(*args))
    with pytest.raises(ValueError, match="no router score"):
        route(*args, **kw, score="relu")
