"""IEEE binary64 arithmetic for the jax scoring backend, on any device.

The backend's contract is bit-equality with the numpy scoring path, so its
device programs need IEEE f64: correctly rounded (to nearest, ties to even)
adds and multiplies, and the full f64 exponent range. CPUs and GPUs have it
in hardware. A TPU does not: XLA emulates f64 there with pairs of f32, which
carry about 48 mantissa bits and the f32 exponent range — an f64 value does
not even survive a round trip to the chip unchanged.

So the backend writes its programs against one of two arithmetics, picked
from the platform:

* :data:`NATIVE` — ``float64`` arrays and XLA's own operators;
* :data:`SOFT` — every value is the ``int64`` bit pattern of its f64, and
  add / multiply / compare are carried out with integer operations (exact
  on every platform, emulated 64-bit integers included), rounding exactly
  as IEEE does.

Both give the same bits. Inputs are finite or +inf; no NaN reaches here.
"""
from __future__ import annotations

import functools

import numpy as np

_FRAC = (1 << 52) - 1
_IMPLICIT = 1 << 52
_MAG = (1 << 63) - 1  # every bit but the sign
_INF_BITS = 0x7FF << 52
_SIGN = -(1 << 63)  # the sign bit, as an int64
_NEG_INF_BITS = _INF_BITS | _SIGN


def _traced_once(fn):
    """``fn`` as a nested ``jax.jit``: inside a program it is traced once per
    argument shape and dtype, not at every call site. A soft operation is
    dozens of integer ops, and tracing them anew at each of a program's call
    sites was most of the time it took to build (or to look up in the
    compile cache) a scoring program. XLA inlines the nested calls, so the
    compiled program computes the same ops and the same bits."""
    jitted = []

    @functools.wraps(fn)
    def call(*args):
        if not jitted:
            import jax

            jitted.append(jax.jit(fn))
        return jitted[0](*args)

    return call


class _Native:
    """Hardware f64 (CPU, GPU)."""

    name = "native"
    # scan unrolling amortizes XLA's per-step loop overhead on the CPU; it
    # changes code size only, never op order or results
    unroll = 16

    @staticmethod
    def encode(x) -> np.ndarray:
        return np.asarray(x, dtype=np.float64)

    @staticmethod
    def decode(x) -> np.ndarray:
        return np.asarray(x, dtype=np.float64)

    @staticmethod
    def const(x: float):
        import jax.numpy as jnp

        return jnp.float64(x)

    @staticmethod
    def from_bits(x):
        """On the device: the f64 values whose int64 bit patterns are ``x``."""
        import jax.numpy as jnp
        from jax import lax

        return lax.bitcast_convert_type(x, jnp.float64)

    @staticmethod
    def to_bits(x):
        """On the device: the int64 bit patterns of the f64 values ``x``."""
        import jax.numpy as jnp
        from jax import lax

        return lax.bitcast_convert_type(x, jnp.int64)

    @staticmethod
    def add(a, b):
        return a + b

    @staticmethod
    def sub(a, b):
        return a - b

    @staticmethod
    def mul(a, b):
        return a * b

    @staticmethod
    def lt(a, b):
        return a < b

    @staticmethod
    def le(a, b):
        return a <= b

    @staticmethod
    def key(a):
        """A value whose order is the numeric order (for min/argmin/max)."""
        return a

    @staticmethod
    def min(a, axis=None):
        import jax.numpy as jnp

        return jnp.min(a, axis=axis)

    @staticmethod
    def max(a, axis=None):
        import jax.numpy as jnp

        return jnp.max(a, axis=axis)


class _Soft:
    """f64 as int64 bit patterns, with integer-only IEEE arithmetic."""

    name = "soft"
    # each operation is dozens of integer ops: unrolled scan bodies would
    # multiply compile time (minutes per shape) for no gain
    unroll = 1

    @staticmethod
    def encode(x) -> np.ndarray:
        return np.asarray(x, dtype=np.float64).view(np.int64)

    @staticmethod
    def decode(x) -> np.ndarray:
        return np.asarray(x, dtype=np.int64).view(np.float64)

    @staticmethod
    def const(x: float):
        import jax.numpy as jnp

        return jnp.int64(np.float64(x).view(np.int64))

    @staticmethod
    def from_bits(x):
        # the values already are their bit patterns
        return x

    @staticmethod
    def to_bits(x):
        return x

    @staticmethod
    @_traced_once
    def key(a):
        # sign-magnitude -> two's-complement order; -0.0 and +0.0 tie
        import jax.numpy as jnp

        return jnp.where(a < 0, -(a & _MAG), a)

    @staticmethod
    @_traced_once
    def lt(a, b):
        return _Soft.key(a) < _Soft.key(b)

    @staticmethod
    @_traced_once
    def le(a, b):
        return _Soft.key(a) <= _Soft.key(b)

    @classmethod
    def min(cls, a, axis=None):
        import jax.numpy as jnp

        k = cls.key(a)
        i = jnp.argmin(k, axis=axis, keepdims=axis is not None)
        if axis is None:
            return a.reshape(-1)[i]
        return jnp.take_along_axis(a, i, axis=axis).squeeze(axis)

    @classmethod
    def max(cls, a, axis=None):
        import jax.numpy as jnp

        k = cls.key(a)
        i = jnp.argmax(k, axis=axis, keepdims=axis is not None)
        if axis is None:
            return a.reshape(-1)[i]
        return jnp.take_along_axis(a, i, axis=axis).squeeze(axis)

    @staticmethod
    def _round(sign, e, s):
        """Pack sign, biased exponent ``e`` (>= 1) and a significand ``s``
        carrying 3 extra bits (guard, round, sticky; leading bit at 55 for
        normal results) into f64 bits, rounding to nearest, ties to even."""
        import jax.numpy as jnp

        low = s & 7
        m = s >> 3
        up = (low > 4) | ((low == 4) & ((m & 1) == 1))
        m = m + up.astype(jnp.int64)
        carry = m >= (1 << 53)
        m = jnp.where(carry, m >> 1, m)
        e = e + carry.astype(jnp.int64)
        field = jnp.where(m >= _IMPLICIT, e, 0)  # else subnormal (e == 1)
        bits = jnp.where(e >= 0x7FF, _INF_BITS, (field << 52) | (m & _FRAC))
        return jnp.where(sign, bits | _SIGN, bits)

    @staticmethod
    def _shift_right_sticky(x, d):
        """``x >> d`` with the shifted-out bits OR-ed into bit 0."""
        import jax.numpy as jnp

        d = jnp.minimum(d, 62)
        lost = (x & ((jnp.int64(1) << d) - 1)) != 0
        return (x >> d) | lost.astype(jnp.int64)

    @staticmethod
    @_traced_once
    def add(a, b):
        import jax.numpy as jnp
        from jax import lax

        a, b = jnp.broadcast_arrays(jnp.asarray(a, jnp.int64), jnp.asarray(b, jnp.int64))
        ma, mb = a & _MAG, b & _MAG
        swap = mb > ma
        x, y = jnp.where(swap, mb, ma), jnp.where(swap, ma, mb)
        sa, sb = a < 0, b < 0
        sx, sy = jnp.where(swap, sb, sa), jnp.where(swap, sa, sb)
        ex, ey = x >> 52, y >> 52
        fx = (x & _FRAC) | jnp.where(ex > 0, _IMPLICIT, 0)
        fy = (y & _FRAC) | jnp.where(ey > 0, _IMPLICIT, 0)
        ex, ey = jnp.maximum(ex, 1), jnp.maximum(ey, 1)
        fy = _Soft._shift_right_sticky(fy << 3, ex - ey)
        fx = fx << 3
        same = sx == sy
        s = jnp.where(same, fx + fy, fx - fy)
        e = ex
        # carry out of an addition: renormalise right, keeping the sticky bit
        over = s >= (1 << 56)
        s = jnp.where(over, (s >> 1) | (s & 1), s)
        e = e + over.astype(jnp.int64)
        # cancellation in a subtraction: renormalise left, not below e == 1
        lz = jnp.maximum(lax.clz(s) - 8, 0)
        sh = jnp.where(s == 0, 0, jnp.minimum(lz, e - 1))
        s = s << sh
        e = e - sh
        out = _Soft._round(sx, e, s)
        zero = jnp.where(sx & sy, _SIGN, jnp.int64(0))
        out = jnp.where(s == 0, zero, out)
        return jnp.where(x >> 52 == 0x7FF, jnp.where(sx, x | _SIGN, x), out)

    @classmethod
    def sub(cls, a, b):
        import jax.numpy as jnp

        return cls.add(a, jnp.asarray(b, jnp.int64) ^ _SIGN)

    @staticmethod
    @_traced_once
    def mul(a, b):
        import jax.numpy as jnp
        from jax import lax

        a, b = jnp.broadcast_arrays(jnp.asarray(a, jnp.int64), jnp.asarray(b, jnp.int64))
        sign = (a < 0) ^ (b < 0)
        ma, mb = a & _MAG, b & _MAG
        ea, eb = ma >> 52, mb >> 52
        fa = (ma & _FRAC) | jnp.where(ea > 0, _IMPLICIT, 0)
        fb = (mb & _FRAC) | jnp.where(eb > 0, _IMPLICIT, 0)
        # normalise subnormal inputs: leading bit to position 52
        na = jnp.where(fa == 0, 0, lax.clz(fa) - 11)
        nb = jnp.where(fb == 0, 0, lax.clz(fb) - 11)
        fa, fb = fa << na, fb << nb
        e = jnp.maximum(ea, 1) - na + jnp.maximum(eb, 1) - nb - 1023
        # 106-bit product fa*fb = H * 2^54 + L from 27-bit halves
        ah, al = fa >> 27, fa & ((1 << 27) - 1)
        bh, bl = fb >> 27, fb & ((1 << 27) - 1)
        mid = ah * bl + al * bh
        t = al * bl + ((mid & ((1 << 27) - 1)) << 27)
        lo = t & ((1 << 54) - 1)
        hi = ah * bh + (mid >> 27) + (t >> 54)
        # the product lies in [2^104, 2^106): bring its leading bit to 55
        top = hi >= (1 << 51)  # product >= 2^105
        k = jnp.where(top, 50, 49)
        s = (hi << (54 - k)) | (lo >> k) | ((lo & ((jnp.int64(1) << k) - 1)) != 0)
        e = e + top.astype(jnp.int64)
        # results below the normal range: shift into the subnormal range
        s = jnp.where(e < 1, _Soft._shift_right_sticky(s, 1 - e), s)
        e = jnp.maximum(e, 1)
        out = _Soft._round(sign, e, s)
        zero = jnp.where(sign, _SIGN, jnp.int64(0))
        out = jnp.where((fa == 0) | (fb == 0), zero, out)
        inf = (ea == 0x7FF) | (eb == 0x7FF)
        return jnp.where(inf, jnp.where(sign, _NEG_INF_BITS, _INF_BITS), out)


NATIVE = _Native
SOFT = _Soft


def for_platform(platform: str):
    """The arithmetic with IEEE f64 on ``platform``: a TPU has none."""
    return SOFT if platform == "tpu" else NATIVE
