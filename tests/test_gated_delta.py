"""``ops/gated_delta.py``: the chunked WY form of the gated delta rule
against the recurrence step by step, forward and every gradient, at small
sizes on the CPU, float32 at ``highest``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.gated_delta import (_unit_lower_inverse, gated_delta_rule,
                                       gated_delta_rule_reference)

KEY_HEADS, HEADS, DK, DV = 2, 4, 16, 8


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(length, seed=0, decay=1.0):
    """Normalised keys and queries as the model hands them over, decays of
    ``decay`` times a softplus, write strengths in (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (length, KEY_HEADS, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (length, KEY_HEADS, DK)))
    v = jax.random.normal(ks[2], (length, HEADS, DV))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (length, HEADS)))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (length, HEADS)))
    return q, k, v, g, beta


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))


# a multiple of both chunks, and lengths that end inside a chunk
@pytest.mark.parametrize("length", [32, 37, 50])
@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunked_form_is_the_recurrence(length, chunk):
    """1e-5 of the largest output: both sides float32 at ``highest``; the
    chunked form sums in another order (measured 1e-7)."""
    inputs = _inputs(length)
    got = jax.jit(lambda *a: gated_delta_rule(*a, chunk=chunk))(*inputs)
    want = jax.jit(gated_delta_rule_reference)(*inputs)
    assert got.shape == (length, HEADS, DV)
    assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("length", [32, 37])
@pytest.mark.parametrize("chunk", [8, 16])
def test_every_gradient_is_the_recurrences(length, chunk):
    """1e-4 of each gradient's largest element: the chunked backward pass
    goes through the inverse's products and recomputes each chunk - float32
    sums in another order (measured 5e-7), ten times the room of the
    output's."""
    inputs = _inputs(length, seed=1)
    probe = jax.random.normal(jax.random.key(7), (length, HEADS, DV))

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * probe),
                                argnums=(0, 1, 2, 3, 4)))(*inputs)

    got = grads(lambda *a: gated_delta_rule(*a, chunk=chunk))
    want = grads(gated_delta_rule_reference)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert _rel(a, b) < 1e-4, name


@pytest.mark.parametrize("decay", [1e-3, 20.0])
def test_slow_and_fast_decays(decay):
    """Decays near 1 let a state live across every chunk; decays near the
    published start's (``A`` up to 16) underflow ``exp(G)`` inside a chunk
    - the mask before the exponential keeps it finite."""
    inputs = _inputs(48, seed=2, decay=decay)
    got = gated_delta_rule(*inputs, chunk=16)
    want = gated_delta_rule_reference(*inputs)
    assert np.all(np.isfinite(np.asarray(got)))
    assert _rel(got, want) < 1e-5


def test_the_state_is_carried_and_corrected():
    """Not a decayed sum: with ``beta = 1`` and ``g = 0`` a key written twice
    holds the second value alone (the delta rule replaces what the state
    predicted), and the state crosses chunk boundaries."""
    length = 24
    q, k, v, g, beta = _inputs(length, seed=3)
    k = k.at[:].set(k[0])  # every position writes one key
    q = q.at[:].set(k[0] * DK ** -0.5)
    g, beta = jnp.zeros_like(g), jnp.ones_like(beta)
    out = gated_delta_rule(q, k, v, g, beta, chunk=8)
    # reading the key back gives the last value written, scaled by q's norm
    np.testing.assert_allclose(out[-1], v[-1] * DK ** -0.5, atol=1e-5)
    np.testing.assert_allclose(out[9], v[9] * DK ** -0.5, atol=1e-5)


def test_padding_leaves_the_state_as_it_is():
    """A row padded with ``beta = 0``, ``g = 0``, ``k = 0`` gives the same
    outputs at its real positions."""
    q, k, v, g, beta = _inputs(20, seed=4)
    short = gated_delta_rule(q, k, v, g, beta, chunk=8)
    padded = [jnp.pad(x, ((0, 4),) + ((0, 0),) * (x.ndim - 1))
              for x in (q, k, v, g, beta)]
    longer = gated_delta_rule(*padded, chunk=8)
    np.testing.assert_allclose(longer[:20], short, atol=1e-6)


@pytest.mark.parametrize("size", [8, 16, 64, 128])
def test_the_product_is_the_unit_lower_inverse(size):
    """Entries of the size a chunk's ``beta k_i k_j D_ij`` has with
    normalised keys of 128 channels."""
    lower = jnp.tril(jax.random.normal(jax.random.key(size),
                                       (3, size, size)) / 11.3, -1)
    inverse = _unit_lower_inverse(lower)
    eye = np.broadcast_to(np.eye(size), (3, size, size))
    np.testing.assert_allclose(inverse @ (jnp.eye(size) + lower), eye,
                               atol=1e-5)


def test_key_heads_must_divide_the_value_heads():
    q, k, v, g, beta = _inputs(8)
    with pytest.raises(ValueError, match="do not divide"):
        gated_delta_rule(q, k, v[:, :3], g[:, :3], beta[:, :3])
