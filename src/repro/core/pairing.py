"""Szudzik pairing / unpairing and walk-triplet encoding (paper §2, §4.2-4.3).

A walk triplet (w, p, v_next) is encoded as one integer:

    f(w, p)  = w * l + p                         (linear packing, paper §4.3)
    code     = Szudzik(f(w, p), v_next)          (single pairing invocation)

Szudzik(x, y) = y^2 + x      if x <  y
              = x^2 + x + y  if x >= y

For N-bit operands the code fits in 2N bits — with 32-bit f and vertex ids the code
is a uint64 (the paper's Aspen-imposed cap; we inherit it deliberately so the Pallas
kernels can represent codes as (hi, lo) u32 lane pairs — TPU has no int64).

Ordering (paper Property 1 / Corollary 1): Szudzik codes order primarily by x + y,
so for a fixed f the codes of all triplets (f, v') lie inside
[Szudzik(f, v_min), Szudzik(f, v_max)] — the basis of the FINDNEXT range search.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

U64 = jnp.uint64
U32 = jnp.uint32
_ONE = jnp.asarray(1, U64)
_TWO = jnp.asarray(2, U64)


def isqrt_u64(z):
    """floor(sqrt(z)) for uint64 arrays.

    float64 sqrt gives ~52 bits of mantissa; for z close to 2^64 the estimate can be
    off by a few ULPs, so we correct with integer Newton steps followed by a final
    clamp. Exact for all uint64 inputs (property-tested).
    """
    z = jnp.asarray(z, U64)
    # Initial estimate via float64 (x64 enabled in repro.core).
    r = jnp.sqrt(z.astype(jnp.float64)).astype(U64)
    r = jnp.maximum(r, _ONE)
    # Newton: r <- (r + z // r) // 2. Converges from above; 4 steps suffice after a
    # float64 seed (error <= a few units).
    for _ in range(4):
        r = jnp.maximum((r + z // jnp.maximum(r, _ONE)) // _TWO, _ONE)
    # isqrt(2^64-1) = 2^32-1, so clamp before squaring: (2^32-1)^2 < 2^64 never
    # wraps, whereas the float seed / Newton can land on 2^32 whose square does.
    max_root = jnp.asarray(0xFFFFFFFF, U64)
    r = jnp.minimum(r, max_root)
    # Final correction: ensure r^2 <= z < (r+1)^2.
    r = jnp.where(r * r > z, r - _ONE, r)
    r = jnp.where(r * r > z, r - _ONE, r)
    rp1 = r + _ONE
    bump = (rp1 <= max_root) & (rp1 * rp1 <= z)
    r = jnp.where(bump, rp1, r)
    # r = 2^32-1 is correct for all z >= (2^32-1)^2 (can't bump past it)
    r = jnp.where(z == 0, jnp.zeros_like(r), r)
    return r


def szudzik_pair(x, y):
    """Szudzik(x, y) for uint64 arrays (operands must be < 2^32)."""
    x = jnp.asarray(x, U64)
    y = jnp.asarray(y, U64)
    return jnp.where(x < y, y * y + x, x * x + x + y)


def unpair_limbs(z):
    """`szudzik_unpair` in 32-bit limbs: the exact bit-restoration isqrt of
    kernels/szudzik.py on (hi, lo) halves — no float64 and no 64-bit
    division, which the TPU emulates at great compile-time and run-time
    cost. Exact for every uint64 z (both results of an unpair fit 32 bits)."""
    from repro.kernels.szudzik import szudzik_unpair_math
    z = jnp.asarray(z, U64)
    hi = (z >> jnp.asarray(32, U64)).astype(U32)
    lo = z.astype(U32)
    x, y = szudzik_unpair_math(hi, lo, unroll=False)
    return x.astype(U64), y.astype(U64)


def szudzik_unpair(z):
    """Inverse of szudzik_pair: returns (x, y) uint64 arrays.

    On TPU this is `unpair_limbs` (native 32-bit ops); elsewhere
    `szudzik_unpair_u64`, which XLA's CPU backend runs ~40x faster. Both are
    exact, so the choice never changes a result."""
    if jax.default_backend() == "tpu":
        return unpair_limbs(z)
    return szudzik_unpair_u64(z)


def szudzik_unpair_u64(z):
    """`szudzik_unpair` as plain uint64 math (float64 seed + Newton isqrt)
    on every platform: the reference form, independent of the kernels'
    limb isqrt."""
    z = jnp.asarray(z, U64)
    s = isqrt_u64(z)
    rem = z - s * s
    # rem < s  -> (x, y) = (rem, s)       [x < y branch]
    # rem >= s -> (x, y) = (s, rem - s)   [x >= y branch]
    x = jnp.where(rem < s, rem, s)
    y = jnp.where(rem < s, s, rem - s)
    return x, y


def cantor_pair(x, y):
    """Cantor pairing (paper §2 mentions it; Property 1 as *stated* holds for
    Cantor — ordering by x+y then x). Wharf adopts Szudzik for its 2N-bit range
    guarantee; Szudzik instead orders by max(x, y). The operative property the
    FINDNEXT range search needs is monotonicity of Szudzik(f, ·) in the second
    argument — see `search_range` and tests/test_pairing.py. Documented as a
    paper erratum in DESIGN.md."""
    x = jnp.asarray(x, U64)
    y = jnp.asarray(y, U64)
    s = x + y
    return s * (s + _ONE) // _TWO + y


def pack_wp(w, p, length):
    """f(w, p) = w * l + p (paper §4.3)."""
    return jnp.asarray(w, U64) * jnp.asarray(length, U64) + jnp.asarray(p, U64)


def unpack_wp(f, length):
    """Invert f(w, p): w = floor(f / l), p = f mod l."""
    f = jnp.asarray(f, U64)
    length = jnp.asarray(length, U64)
    return f // length, f % length


def encode_triplet(w, p, v_next, length):
    """Encode walk triplet (w, p, v_next) -> uint64 code (one Szudzik invocation)."""
    return szudzik_pair(pack_wp(w, p, length), v_next)


def decode_triplet(code, length):
    """Decode uint64 code -> (w, p, v_next)."""
    f, v_next = szudzik_unpair(code)
    w, p = unpack_wp(f, length)
    return w, p, v_next


def search_range(f, v_min, v_max):
    """FINDNEXT search bounds [lb, ub] (paper §5.1).

    By Corollary 1 every code with first operand f and second operand in
    [v_min, v_max] lies within [Szudzik(f, v_min), Szudzik(f, v_max)].
    """
    return szudzik_pair(f, v_min), szudzik_pair(f, v_max)


# ---------------------------------------------------------------------------
# (hi, lo) u32 lane-pair helpers — the TPU-native code representation used by
# the Pallas kernels (TPU has no 64-bit integers).
# ---------------------------------------------------------------------------

def split_u64(code):
    """uint64 -> (hi, lo) uint32."""
    code = jnp.asarray(code, U64)
    return (code >> jnp.asarray(32, U64)).astype(U32), (
        code & jnp.asarray(0xFFFFFFFF, U64)
    ).astype(U32)


def join_u64(hi, lo):
    """(hi, lo) uint32 -> uint64."""
    return (jnp.asarray(hi, U64) << jnp.asarray(32, U64)) | jnp.asarray(lo, U64)
