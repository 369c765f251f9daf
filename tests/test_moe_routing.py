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


def _weights(seed=0, experts=E, bias_scale=0.1, tokens=T):
    ks = jax.random.split(jax.random.key(seed), 6)
    return {"s": jax.random.normal(ks[0], (tokens, D)),
            "router": 0.3 * jax.random.normal(ks[1], (D, experts)),
            "bias": bias_scale * jax.random.normal(ks[2], (experts,)),
            "w1": 0.2 * jax.random.normal(ks[3], (experts, D, W)),
            "w3": 0.2 * jax.random.normal(ks[4], (experts, D, W)),
            "w2": 0.2 * jax.random.normal(ks[5], (experts, W, D))}


def _share(p, first, count, block=moe.BLOCK, rows=False):
    """The program's layer on the share ``first .. first + count - 1``, in
    blocks of at most ``block`` rows (the layer reads ``BLOCK`` as it is
    traced), so that 48 tokens fill several blocks an expert: ``(y,
    load)``, and the rows the loops ran after them if ``rows``."""
    held = slice(first, first + count)
    with mock.patch.object(moe, "BLOCK", block):
        out = routed_experts(p["s"], p["router"], p["bias"], p["w1"][held],
                             p["w3"][held], p["w2"][held], top_k=K,
                             experts_held=(first, count))
    return out if rows else out[:2]


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


def _layout(p, first, count, block):
    """What the plan's blocks hold for the share: padding rows, a token whose
    held choices fall in different blocks, an expert whose rows span several
    blocks with a part-filled last one."""
    chosen, _ = route(p["s"], p["router"], p["bias"], top_k=K,
                      norm_topk=True, scale=1.0)
    with mock.patch.object(moe, "BLOCK", block):
        plan = moe._plan(chosen, first, count, E)
    n_run = int(plan.n_run)
    valid = np.asarray(plan.valid[:n_run])
    tokens = np.asarray(plan.tokens[:n_run])
    expert = np.asarray(plan.expert[:n_run])
    blocks_of = {}
    for j, lane in zip(*np.nonzero(valid)):
        blocks_of.setdefault(tokens[j, lane], set()).add(j)
    real = valid.sum(axis=1)
    return {"padding": not valid.all(),
            "split_token": any(len(b) > 1 for b in blocks_of.values()),
            "spanning_expert": any(
                np.sum(expert == e) > 1
                and real[np.nonzero(expert == e)[0][-1]] < valid.shape[1]
                for e in range(count))}


@pytest.mark.parametrize("first, count", [(0, 8), (2, 4)])
@pytest.mark.parametrize("block", [8, 16, 512])
def test_every_gradient_equals_the_dense_sums(first, count, block):
    """The value and all five gradients, over blocks that hold padding rows
    and tokens whose held choices fall in different blocks, and, in blocks
    of 8, experts that span several blocks with a part-filled last one."""
    p = _weights(seed=5)
    layout = _layout(p, first, count, block)
    assert layout["padding"] and layout["split_token"]
    assert layout["spanning_expert"] or block > 8
    y = jax.jit(lambda p: _share(p, first, count, block=block)[0])(p)
    assert _rel(y, _dense(p, first, count)[0]) < 1e-5
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
    """96 pairs fill a dozen blocks of 8 and a part of one of the rule's 64
    (``BLOCK`` 512): the regime of many small experts, a few rows a
    block."""
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
    # 96 pairs over 8 experts: the rule's floor of 64 under ``BLOCK`` 512
    for block, size in ((8, 8), (16, 16), (512, 64)):
        with mock.patch.object(moe, "BLOCK", block):
            plan = moe._plan(chosen, 2, 4, E)
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


#: the first 16 hex digits of sha256(str(jaxpr)) of the layer's ``(y,
#: load)`` and of its five gradients for a call shaped like
#: ``models/lfm2_moe.py``'s - top-4 of 32 experts, 8 held, no epsilon named -
#: each jaxpr cut to the equations its outputs need (the rows the loops ran
#: are a third output, dead here). At the published call (4,096 tokens, d
#: 2,048, experts of 1,792) the block is the ceiling of 512, and the digests
#: are the parent commit's: lfm2 runs the parent's grouped products. At the
#: toy call (128 tokens, d 32, experts of 48) the block follows the expected
#: load down to the floor of 64 where the parent's was 512, so the digests
#: pin that program
LFM2_SHAPED = {
    ("published", "layer"): "b64499fde627febe",
    ("published", "gradients"): "960a3de73aef4a79",
    ("toy", "layer"): "66fdf26ac789dc7d",
    ("toy", "gradients"): "69a0e10573efc392"}
LFM2_CALLS = {
    "toy": ((2, 64, 32), (32, 32), (32,), (8, 32, 48), (8, 32, 48),
            (8, 48, 32)),
    "published": ((1, 4096, 2048), (2048, 32), (32,), (8, 2048, 1792),
                  (8, 2048, 1792), (8, 1792, 2048))}


def _lfm2_shaped(what, call="toy"):
    """The layer's ``(y, load)`` or its five gradients at ``LFM2_SHAPED``'s
    call, and the abstract arguments: nothing is computed."""
    def layer(s, router, bias, w1, w3, w2):
        return routed_experts(s, router, bias, w1, w3, w2, top_k=4,
                              experts_held=(0, 8), norm_topk=True,
                              scale=1.0)[:2]

    def gradients(*args):
        return jax.grad(lambda *a: jnp.sum(layer(*a)[0] ** 2),
                        argnums=(0, 1, 3, 4, 5))(*args)

    args = [jax.ShapeDtypeStruct(shape, jnp.float32)
            for shape in LFM2_CALLS[call]]
    return {"layer": layer, "gradients": gradients}[what], args


@pytest.mark.parametrize("call, what", sorted(LFM2_SHAPED))
def test_with_the_defaults_the_traced_program_is_the_parents(call, what):
    import hashlib

    from jax._src.interpreters import partial_eval as pe

    fn, args = _lfm2_shaped(what, call)
    with jax.default_matmul_precision(None):
        closed = jax.make_jaxpr(fn)(*args)
    needed, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.out_avals),
                             instantiate=True)
    got = hashlib.sha256(str(needed).encode()).hexdigest()[:16]
    assert got == LFM2_SHAPED[call, what]


def _avals(jaxpr):
    """Every value the equations of ``jaxpr`` and of the jaxprs inside them
    make."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _avals(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _avals(sub)


@pytest.mark.parametrize("what", ["gradients", "layer"])
@pytest.mark.parametrize("block", [16, moe.BLOCK])
def test_the_combine_holds_no_worst_case_rows_nor_a_row_a_choice(what,
                                                                 block):
    """Each block's rows go into the output inside the loop: neither the
    layer nor its gradients hold an array of the plan's static worst case,
    ``blocks_max x block`` rows, nor one of ``[tokens, top_k, d]``."""
    tokens, top_k, width, held = 128, 4, 32, 8
    fn, args = _lfm2_shaped(what)
    with mock.patch.object(moe, "BLOCK", block):
        size = moe._block(tokens, top_k, 32)
        worst = (tokens * min(top_k, held) // size + held) * size
        shapes = {tuple(a.shape) for a in _avals(jax.make_jaxpr(fn)(
            *args).jaxpr) if hasattr(a, "shape")}
    assert (tokens, top_k, width) not in shapes
    assert not [shape for shape in shapes if shape and shape[0] == worst]
    assert (tokens, width) in shapes  # the output the blocks add into


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

    y, load, _ = jax.jit(share)(p)
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


# -- the block follows the call's expected load per held expert --------------------

@pytest.mark.parametrize("tokens, top_k, experts, ceiling, block", [
    (2048, 10, 512, 512, 64),    # Qwen3-Next: 40 pairs an expert, 60 at peak
    (2048, 6, 128, 512, 256),    # kanana: 96, 144
    (4096, 4, 32, 512, 512),     # LFM2: 512, 768 - the ceiling
    (48, 2, 8, 512, 64),         # 12, 18: the floor
    (16, 2, 8, 512, 32),         # the floor is more than the call's 32 pairs
    (256, 1, 3, 512, 128),       # 1.5 x expected is 128 exactly
    (258, 1, 3, 512, 256),       # and 129
    (2048, 10, 512, 16, 16),     # a patched ``BLOCK`` is still the ceiling
    (4096, 4, 32, 8, 8)])
def test_the_block_is_the_power_of_two_over_one_and_a_half_loads(
        tokens, top_k, experts, ceiling, block):
    with mock.patch.object(moe, "BLOCK", ceiling):
        assert moe._block(tokens, top_k, experts) == block


def _small_loads(first, count, score):
    """96 tokens, top-2 of 8: 24 pairs a held expert expected, so the rule
    takes blocks of 64. Expert 0 is everybody's choice (96 pairs: one full
    block and a part of a second), expert 3 nobody's."""
    p = _weights(seed=18, bias_scale=0.0, tokens=96)
    if score == "softmax":  # every token leans on u, which expert 0 reads
        u = jax.random.normal(jax.random.key(20), (D,))
        u = u / jnp.linalg.norm(u)
        p["s"] = p["s"] + 5.0 * u
        p["router"] = p["router"].at[:, 0].set(3.0 * u).at[:, 3].set(-3.0 * u)
        p["bias"] = None
    else:
        p["bias"] = jnp.zeros(E).at[0].set(10.0).at[3].set(-10.0)
    held = slice(first, first + count)

    def share(p):
        return routed_experts(
            p["s"], p["router"], p["bias"], p["w1"][held], p["w3"][held],
            p["w2"][held], top_k=K, experts_held=(first, count),
            eps=0.0 if score == "softmax" else 1e-6, score=score)

    return p, share


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
@pytest.mark.parametrize("first, count", [(0, 4), (0, 8)])
def test_blocks_of_64_under_small_loads_equal_the_masked_dense_sum(
        first, count, score):
    """The forward and all five gradients where the expected load is far
    under the block, with one expert spilling into a second block and one
    held expert without a row; ``rows`` is what the loops ran."""
    p, share = _small_loads(first, count, score)
    assert moe._block(96, K, E) == 64
    dense = _dense_softmax if score == "softmax" else _dense
    y, load, rows = jax.jit(share)(p)
    want, chosen = dense(p, first, count)
    load = np.asarray(load)
    assert load[0] == 96 and load[3] == 0
    np.testing.assert_array_equal(load, [
        int(jnp.sum(chosen == e)) for e in range(first, first + count)])
    assert int(rows) == 64 * int(np.sum(-(-load // 64))) == 64 * (
        2 + int(np.sum(load[1:] > 0)))
    assert _rel(y, want) < 1e-5
    probe = jax.random.normal(jax.random.key(19), p["s"].shape)
    got = jax.jit(jax.grad(lambda p: jnp.sum(share(p)[0] * probe)))(p)
    ref = jax.jit(jax.grad(lambda p: jnp.sum(
        dense(p, first, count)[0] * probe)))(p)
    for name in ("s", "router", "w1", "w3", "w2"):
        assert _rel(got[name], ref[name]) < 1e-5, name
    # the held expert without a row gets no weight gradient
    assert not np.any(np.asarray(got["w1"][3]))
