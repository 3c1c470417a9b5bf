"""Double-fp32 vectors: the wide vectors of Mix-V3 on a chip without FP64.

Callipepla's answer to its third challenge (paper §6) keeps the matrix
narrow and the main-loop vectors wide.  TPU v5e has no FP64 ALUs, so a
wide vector here is an unevaluated sum of fp32 words:

* :class:`DF` — ``hi + lo`` with ``|lo| ≤ ulp(hi)/2``: a ~48-bit
  significand, for r, p, z, Ap, b, the diagonal and every scalar;
* :class:`TF` — three words, for x alone.  On a 1000 × 1000 grounded
  grid Laplacian, |x| reaches 1.2e6 while the paper's bound asks
  ``||b − A x|| ≤ 1e-9 ||b||``; rounding the float64 solution to two
  fp32 words already leaves a true relative residual of 3.4e-9, so x
  needs a third.

Every operation is built from error-free transforms: :func:`two_sum`
(six adds, exact for any fp32 pair) and products of operands of at most
12 significant bits (:func:`split` truncates a word's mantissa with a
bit mask), which fp32 holds exactly.  An exact product gives the same
bits whether or not a compiler fuses it into a following add (XLA:CPU
does, shape-dependently), so results do not depend on fusion.  The few
products that round on purpose go through :func:`rounded_mul`.

Both classes are pytrees with the arithmetic operators the phase code
uses (``+``, ``-``, ``*``, ``/``, unary ``-``, indexing), so
:func:`repro.core.phases.vsr_iteration` and the stream VM's
straight-line program run on them unchanged; :func:`select` is
``jnp.where`` for them, and :meth:`DF.dot` the lane-wise dot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DF", "TF", "two_sum", "fast_two_sum", "split", "rounded_mul",
           "two_prod", "pair_add", "df_from_f64", "tf_from_f64", "to_f64",
           "select", "is_wide", "hi"]

_F32 = jnp.float32
#: keeps the sign, the exponent and the top 11 stored mantissa bits
_HI12 = np.int32(-4096)          # 0xFFFFF000


def two_sum(a, b):
    """``s + e == a + b`` exactly, ``s = fl(a + b)`` (Knuth)."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def fast_two_sum(a, b):
    """:func:`two_sum` for ``|a| ≥ |b|`` (or ``a`` zero), in three adds."""
    s = a + b
    return s, b - (s - a)


def split(a):
    """``a = h + l`` with ``h`` the top 12 significant bits of ``a`` and
    ``l`` the rest (at most 12 bits), by masking the mantissa: no
    product, so no compiler can fuse it away."""
    h = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(a, jnp.int32) & _HI12, a.dtype)
    return h, a - h


def rounded_mul(a, b):
    """``fl(a · b)`` whether or not the compiler contracts the product
    into a following add: the only contractible use is an add of ±0,
    and ``fma(a, b, ±0) ≡ fl(a · b)`` (the trick of
    :func:`repro.core.batch.rounded_products`)."""
    return a * b + b * jnp.zeros((), b.dtype)


def two_prod(a, b):
    """``p + e == a · b`` exactly, ``p = fl(a · b)`` (Dekker): every
    partial product of the halves is exact, and so is each add."""
    p = rounded_mul(a, b)
    ah, al = split(a)
    bh, bl = split(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


def pair_add(ah, al, bh, bl):
    """Accurate pair + pair (relative error ≤ 3u², Joldes et al. 2017)."""
    s, e = two_sum(ah, bh)
    t, f = two_sum(al, bl)
    s, e = fast_two_sum(s, e + t)
    return fast_two_sum(s, e + f)


@jax.tree_util.register_pytree_node_class
class DF:
    """A double-fp32 array: the value ``hi + lo``."""

    __slots__ = ("hi", "lo")

    def __init__(self, hi, lo):
        self.hi, self.lo = hi, lo

    def tree_flatten(self):
        return (self.hi, self.lo), None

    @classmethod
    def tree_unflatten(cls, _, planes):
        return cls(*planes)

    @classmethod
    def zeros(cls, shape):
        z = jnp.zeros(shape, _F32)
        return cls(z, z)

    @property
    def planes(self):
        return (self.hi, self.lo)

    @property
    def shape(self):
        return self.hi.shape

    @property
    def ndim(self):
        return self.hi.ndim

    def __getitem__(self, idx):
        return DF(self.hi[idx], self.lo[idx])

    @property
    def at(self):
        return _At(self)

    def __neg__(self):
        return DF(-self.hi, -self.lo)

    def __add__(self, o):
        return DF(*pair_add(self.hi, self.lo, o.hi, o.lo))

    def __sub__(self, o):
        return self + (-o)

    def __mul__(self, o):
        h, l = two_prod(self.hi, o.hi)
        cross = rounded_mul(self.hi, o.lo) + rounded_mul(self.lo, o.hi)
        return DF(*fast_two_sum(h, l + cross))

    def __truediv__(self, o):
        q1 = self.hi / o.hi                 # any rounding: the rest is exact
        p = o * DF(q1, jnp.zeros_like(q1))
        r = self - p
        q2 = r.hi / o.hi
        return DF(*fast_two_sum(q1, q2))

    def sum(self):
        """Sum over the last axis: a halving tree of pair adds (the
        bracketing of :func:`repro.core.batch.tree_sum`)."""
        h, l = self.hi, self.lo
        w = h.shape[-1]
        wp = 1 << max(w - 1, 0).bit_length()
        if wp != w:
            pad = [(0, 0)] * (h.ndim - 1) + [(0, wp - w)]
            h, l = jnp.pad(h, pad), jnp.pad(l, pad)
        while wp > 1:
            wp //= 2
            h, l = pair_add(h[..., :wp], l[..., :wp], h[..., wp:],
                            l[..., wp:])
        return DF(h[..., 0], l[..., 0])

    def dot(self, o):
        """Lane-wise dot product over the last axis."""
        return (self * o).sum()


class _At:
    """``v.at[idx].set(w)`` for a pair, as for an array."""

    def __init__(self, v, idx=None):
        self.v, self.idx = v, idx

    def __getitem__(self, idx):
        return _At(self.v, idx)

    def set(self, w):
        v, i = self.v, self.idx
        return DF(v.hi.at[i].set(w.hi), v.lo.at[i].set(w.lo))


@jax.tree_util.register_pytree_node_class
class TF:
    """A triple-fp32 array ``w0 + w1 + w2``: the solution x, which only
    ever takes steps ``x + DF`` (:meth:`__add__`)."""

    __slots__ = ("planes",)

    def __init__(self, w0, w1, w2):
        self.planes = (w0, w1, w2)

    def tree_flatten(self):
        return self.planes, None

    @classmethod
    def tree_unflatten(cls, _, planes):
        return cls(*planes)

    @property
    def shape(self):
        return self.planes[0].shape

    def __add__(self, o):
        w0, w1, w2 = self.planes
        s0, e0 = two_sum(w0, o.hi)
        s1, e1 = two_sum(w1, o.lo)
        s1, e2 = two_sum(s1, e0)
        low = w2 + (e1 + e2)
        h, t = two_sum(s0, s1)
        m, low = two_sum(t, low)
        return TF(h, m, low)


def is_wide(v) -> bool:
    return isinstance(v, (DF, TF))


def hi(v):
    """The leading word of a wide value; a plain array as it is."""
    return v.hi if isinstance(v, DF) else v


def select(mask, a, b):
    """``jnp.where(mask, a, b)`` on every word (plain arrays too)."""
    return jax.tree.map(lambda u, v: jnp.where(mask, u, v), a, b)


# ------------------------------------------------------------ host side
def _words(v, k: int):
    """The first ``k`` fp32 words of float64 ``v`` (host numpy)."""
    rest = np.asarray(v, np.float64)
    out = []
    for _ in range(k):
        w = rest.astype(np.float32)
        out.append(w)
        rest = rest - w.astype(np.float64)
    return out


def df_from_f64(v) -> DF:
    return DF(*(jnp.asarray(w) for w in _words(v, 2)))


def tf_from_f64(v) -> TF:
    return TF(*(jnp.asarray(w) for w in _words(v, 3)))


def to_f64(v) -> np.ndarray:
    """A wide value (or plain array) as float64 on the host: the words
    added from the largest, so two words add exactly."""
    planes = v.planes if is_wide(v) else (v,)
    out = np.asarray(planes[0], np.float64)
    for w in planes[1:]:
        out = out + np.asarray(w, np.float64)
    return out
