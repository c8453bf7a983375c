"""`core.rounding.div`: the quotient every backend agrees on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rounding import div, nearest_quotient

RNG = np.random.default_rng(0)


def _operands(n, dtype):
    a = np.concatenate([RNG.uniform(-1e3, 1e3, n),
                        RNG.integers(1, 2 ** 15, n).astype(float),
                        np.exp(RNG.uniform(-20, 20, n))]).astype(dtype)
    b = np.concatenate([RNG.uniform(-1e3, 1e3, n),
                        RNG.integers(1, 2 ** 15, n).astype(float),
                        np.exp(RNG.uniform(-20, 20, n))]).astype(dtype)
    return a, b


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jnp"])
@pytest.mark.parametrize("ulps", [1, 2])
@pytest.mark.parametrize("step", [np.inf, -np.inf])
def test_nearest_quotient_undoes_ulps(xp, ulps, step):
    """A quotient one or two ulps off either way — the TPU's division
    returns one ulp off for about a third of f32 operands — is moved
    back to the correctly rounded one, and a correct quotient stays
    put."""
    a, b = _operands(20000, np.float32)
    exact = a / b
    off = exact
    for _ in range(ulps):
        off = np.nextafter(off, np.float32(step))
    if xp is np:
        got = nearest_quotient(a, b, off, np)
    else:
        got = jax.jit(lambda a, b, q: nearest_quotient(a, b, q, jnp))(
            a, b, off)
    np.testing.assert_array_equal(np.asarray(got), exact)
    np.testing.assert_array_equal(nearest_quotient(a, b, exact, np),
                                  exact)


def test_nearest_quotient_passes_zero_and_nonfinite():
    a = np.asarray([0.0, 1.0, -1.0, 0.0], np.float32)
    b = np.asarray([3.0, 0.0, 0.0, 0.0], np.float32)
    with np.errstate(divide="ignore", invalid="ignore"):
        q = a / b
        got = nearest_quotient(a, b, q, np)
    np.testing.assert_array_equal(got, q)


@pytest.mark.parametrize("divisor", [40.0, 25.0, 100.0, None])
def test_div_matches_numpy_under_jit(divisor):
    """XLA:CPU turns ``x / 40.0`` into ``x * 0.025``; `div` still
    returns numpy's correctly rounded quotient."""
    a, b = _operands(20000, np.float32)
    if divisor is None:
        got = jax.jit(lambda a, b: div(a, b, jnp))(a, b)
    else:
        b = np.float32(divisor)
        got = jax.jit(lambda a: div(a, divisor, jnp))(a)
    np.testing.assert_array_equal(np.asarray(got), a / b)
    np.testing.assert_array_equal(div(a, b), a / b)
    # python-scalar divisors stay weakly typed
    assert div(jnp.arange(3, dtype=jnp.float32), 25.0, jnp).dtype \
        == jnp.float32
