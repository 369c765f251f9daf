"""``ops/ssd.py``: the chunked dual form of the Mamba-2 recurrence against
the recurrence step by step, values and all five gradients, in float32 at
``highest`` precision on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import ssd
from fedml_tpu.ops.ssd import ssd_scan, ssd_scan_reference

HEADS, DIM, STATE = 4, 16, 16
#: both sides are float32 sums of the same terms in another order: a chunk
#: of 16 adds up to 16 products where the recurrence multiplies 16 decays
#: one after another. The largest relative difference read over these cases
#: is 3.6e-7 (values) and 5.1e-7 (gradients), a few float32 roundings; 1e-5
#: leaves twenty times of room and fails a wrong mask, a decay off by one
#: position or a dropped chunk state by three orders and more
TOL = 1e-5


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(length, groups=1, seed=0):
    keys = jax.random.split(jax.random.key(seed), 5)
    xs = jax.random.normal(keys[0], (length, HEADS, DIM))
    # steps of 0.001 to 0.5, decays of -1 to -16 a unit of time
    dt = jnp.exp(jax.random.uniform(keys[1], (length, HEADS),
                                    minval=np.log(1e-3), maxval=np.log(0.5)))
    a = -jnp.exp(jax.random.uniform(keys[2], (HEADS,), maxval=np.log(16.0)))
    b = jax.random.normal(keys[3], (length, groups, STATE))
    c = jax.random.normal(keys[4], (length, groups, STATE))
    return xs, dt, a, b, c


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


@pytest.mark.parametrize("length, groups", [(64, 1), (40, 1), (64, 2),
                                            (40, 4), (7, 1)])
def test_values_equal_the_recurrence_step_by_step(length, groups):
    """40 and 7 positions: a ragged last chunk, and a row shorter than one
    chunk."""
    args = _inputs(length, groups)
    got = jax.jit(lambda *a: ssd_scan(*a, chunk=16))(*args)
    want = jax.jit(ssd_scan_reference)(*args)
    assert got.shape == want.shape == (length, HEADS, DIM)
    assert _rel(got, want) < TOL


@pytest.mark.parametrize("length, groups", [(64, 1), (40, 2)])
def test_all_five_gradients_equal_the_recurrences(length, groups):
    args = _inputs(length, groups, seed=1)
    weight = jax.random.normal(jax.random.key(9), (length, HEADS, DIM))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)

    got = jax.jit(jax.grad(loss(lambda *a: ssd_scan(*a, chunk=16)),
                           argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.jit(jax.grad(loss(ssd_scan_reference),
                            argnums=(0, 1, 2, 3, 4)))(*args)
    for name, g, w in zip(("xs", "dt", "a", "b", "c"), got, want):
        assert g.shape == w.shape
        assert np.isfinite(np.asarray(g)).all(), name
        assert _rel(g, w) < TOL, name


def test_a_chunk_of_one_and_a_chunk_of_the_row_agree():
    args = _inputs(48, seed=2)
    one = jax.jit(lambda *a: ssd_scan(*a, chunk=1))(*args)
    whole = jax.jit(lambda *a: ssd_scan(*a, chunk=48))(*args)
    sixteen = jax.jit(lambda *a: ssd_scan(*a, chunk=16))(*args)
    assert _rel(one, whole) < TOL and _rel(sixteen, whole) < TOL


def test_a_long_strong_decay_underflows_to_zero_and_not_to_nan():
    """The mask comes before the exponential: above the diagonal the
    exponents are positive, here up to 64 x 8 x 16 = 8,192."""
    xs, dt, a, b, c = _inputs(64, seed=3)
    dt, a = jnp.full_like(dt, 8.0), jnp.full_like(a, -16.0)
    value, grads = jax.jit(jax.value_and_grad(
        lambda *args: jnp.sum(ssd_scan(*args, chunk=32) ** 2),
        argnums=(0, 1, 2, 3, 4)))(xs, dt, a, b, c)
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


def test_the_incoming_state_is_what_ties_the_chunks():
    """The control of the benchmark's check (chunks treated as separate
    rows): without the carried state the first chunk is unchanged and every
    later one is wrong."""
    args = _inputs(48, seed=4)
    sound = ssd_scan(*args, chunk=16)
    chunk = ssd._chunk
    try:
        ssd._chunk = lambda a, state, x: chunk(a, jnp.zeros_like(state), x)
        cut = ssd_scan(*args, chunk=16)
    finally:
        ssd._chunk = chunk
    assert _rel(cut[:16], sound[:16]) < TOL
    assert _rel(cut[16:], sound[16:]) > 1e-2
    separate = jnp.concatenate([
        ssd_scan(*(x[i:i + 16] if x.ndim > 1 else x for x in args), chunk=16)
        for i in (0, 16, 32)])
    assert _rel(cut, separate) < TOL


def test_heads_that_do_not_divide_into_the_groups_are_refused():
    xs, dt, a, _, _ = _inputs(16)
    bad = jnp.zeros((16, 3, STATE))
    with pytest.raises(ValueError, match="groups"):
        ssd_scan(xs, dt, a, bad, bad, chunk=16)


def test_the_program_is_xla_with_one_chunk_rematerialised_at_a_time():
    args = _inputs(64)
    lowered = jax.jit(jax.grad(
        lambda *a: jnp.sum(ssd_scan(*a, chunk=16)),
        argnums=(0, 1, 2, 3, 4))).lower(*args)
    text = lowered.as_text()
    assert "custom_call" not in text and "custom-call" not in text
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd_scan(*a, chunk=16))))(*args))
    assert "checkpoint" in jaxpr or "remat" in jaxpr
    # the decay matrices are one chunk's, never the row's
    assert f"f32[{HEADS},16,16]" in jaxpr
    assert f"f32[4,{HEADS},16,16]" not in jaxpr
    assert f"f32[{HEADS},64,64]" not in jaxpr
