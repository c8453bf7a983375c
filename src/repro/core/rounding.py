"""Division rounded to nearest, the same on the TPU as on the CPU.

Neither XLA backend divides f32 as numpy does by default. The TPU
computes a quotient from a reciprocal estimate: about a third of
quotients land one ulp off the correctly rounded one (measured on a
v5e, CHANGES.md PR 11). XLA:CPU divides correctly but rewrites a
division by a constant into a multiplication by its rounded reciprocal
(``x / 40`` becomes ``x * 0.025``), which is off just as often for
such divisors. The serving plane compares scores for exact ties and
buckets values at exact boundaries, so one ulp is enough for the chip
and the CPU to place an arrival on different servers. `div` returns
the correctly rounded quotient on both: the TPU lowering corrects its
quotient (`nearest_quotient`), every other lowering divides by a
divisor it cannot see as a constant.
"""
from __future__ import annotations

import numpy as np


def _split(x, xp):
    """f32 x = hi + lo exactly, each with at most 12 significant bits:
    hi is x with the low 12 mantissa bits cleared (a bit mask, so no
    compiler rewrite of float arithmetic can undo it)."""
    if xp is np:
        hi = (x.view(np.uint32) & np.uint32(0xFFFFF000)).view(np.float32)
    else:
        import jax
        bits = jax.lax.bitcast_convert_type(x, np.uint32)
        hi = jax.lax.bitcast_convert_type(bits & np.uint32(0xFFFFF000),
                                          np.float32)
    return hi, x - hi


def nearest_quotient(a, b, q, xp):
    """The f32 nearest to the exact ``a / b``, given an f32 quotient
    `q` within two ulps of it (other dtypes pass through, as do zero
    and non-finite quotients). The residual ``a - q*b`` is formed
    exactly with Dekker's two-product: the products of 12-bit halves
    are exact, so the result is the same whether or not the compiler
    fuses a multiply and an add into an FMA."""
    if np.dtype(q.dtype) != np.float32:
        return q
    a = xp.asarray(a, np.float32)
    b = xp.asarray(b, np.float32)
    bh, bl = _split(b, xp)
    half = np.float32(0.5) * xp.abs(b)
    for _ in range(2):                      # one ulp per step
        # q*b == p + e exactly, and a - p is exact because p is within
        # a few ulps of a (Sterbenz)
        p = q * b
        qh, ql = _split(q, xp)
        e = ((qh * bh - p) + qh * bl + ql * bh) + ql * bl
        r = (a - p) - e
        r = xp.where(b < 0, -r, r)          # residual in quotient units
        up = xp.nextafter(q, xp.asarray(np.inf, np.float32))
        dn = xp.nextafter(q, xp.asarray(-np.inf, np.float32))
        near = xp.where(r > (up - q) * half, up,
                        xp.where(r < (dn - q) * half, dn, q))
        q = xp.where(xp.isfinite(q) & (q != 0), near, q)
    return q


def div(a, b, xp=np):
    """``a / b`` rounded to nearest. numpy divides as is. Under jnp the
    TPU lowering corrects its quotient with `nearest_quotient`; every
    other lowering divides by `b` behind an optimization barrier, so
    that a constant divisor is not turned into a reciprocal multiply."""
    if xp is np:
        return a / b
    import jax
    b = xp.asarray(b, xp.result_type(a, b))
    return jax.lax.platform_dependent(
        default=lambda: a / jax.lax.optimization_barrier(b),
        tpu=lambda: nearest_quotient(a, b, a / b, xp))
