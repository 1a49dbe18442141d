"""Pallas TPU kernel: FM-index occupancy count (paper §4.4).

The paper's optimized O_c layout stores each eta=32 bucket as ONE cache
line: 4x4B counts + 32 one-byte bases (+pad).  occ(c, i) is then an AVX2
byte-compare against c followed by a 32-bit popcount of the compare mask.

TPU adaptation: the 32-byte bucket body becomes a 32-lane VREG row; the
compare+popcount becomes a VPU compare + masked lane-sum.  A block of
``qb`` queries is processed per grid cell (QB=256 default; the engine's
occ-layout sweep tries several values on the active backend):

  out[q] = counts[q] + sum_j [ bytes[q, j] == c[q]  AND  j < r[q] ]

``occ_count_packed_pallas_call`` is the same contraction over the
BASELINE eta=128 layout (2-bit packed, 4 bases/byte LSB-first): the
kernel additionally unpacks each 32-byte row into 128 codes — the extra
per-query instructions the paper's Table 4 measures.  The sentinel
correction for that layout (the primary row packs as code 0) is data-
independent of the bucket body and folded into ``base`` by ops.py.

The *gather* of the (bucket -> (counts, bytes)) rows is left to XLA in
ops.py — on TPU a data-dependent gather belongs to the XLA gather engine;
the irregular-latency hiding the paper gets from software prefetching is
obtained here by batching the gathers of a whole lockstep round into one
vectorized load (DESIGN.md §2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QB = 256          # queries per grid cell (default; sweepable)
ETA = 32          # bucket width (paper's optimized compression factor)
BASE_ETA = 128    # baseline bucket width (2-bit packed)


def _occ_kernel_body(bytes_ref, c_ref, r_ref, base_ref, out_ref, *, qb):
    rows = bytes_ref[...].astype(jnp.int32)          # (qb, 32)
    c = c_ref[...]                                   # (qb, 1)
    r = r_ref[...]                                   # (qb, 1)
    base = base_ref[...]                             # (qb, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (qb, ETA), 1)
    m = (rows == c) & (lane < r)
    out_ref[...] = base + jnp.sum(m.astype(jnp.int32), axis=1, keepdims=True)


def _occ_packed_kernel_body(packed_ref, c_ref, r_ref, base_ref, out_ref, *,
                            qb):
    packed = packed_ref[...].astype(jnp.int32)       # (qb, 32) 4 codes/byte
    c = c_ref[...]
    r = r_ref[...]
    base = base_ref[...]
    # unpack LSB-first: byte j holds codes [4j..4j+3] (fmindex.build_index)
    shifts = jnp.arange(4, dtype=jnp.int32) * 2      # (4,)
    codes = (packed[:, :, None] >> shifts) & 3       # (qb, 32, 4)
    codes = codes.reshape(qb, BASE_ETA)
    lane = jax.lax.broadcasted_iota(jnp.int32, (qb, BASE_ETA), 1)
    m = (codes == c) & (lane < r)
    out_ref[...] = base + jnp.sum(m.astype(jnp.int32), axis=1, keepdims=True)


def _occ_call(body, width, bucket_rows, c, r, base, *, qb, interpret):
    # per-query operands are (T, 1) columns: a 1-D (qb,) block does not
    # match the tiling XLA gives a 1-D int32 array on TPU
    T = bucket_rows.shape[0]
    assert T % qb == 0
    grid = (T // qb,)
    col = pl.BlockSpec((qb, 1), lambda g: (g, 0))
    return pl.pallas_call(
        functools.partial(body, qb=qb),
        grid=grid,
        in_specs=[pl.BlockSpec((qb, width), lambda g: (g, 0)), col, col, col],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((T, 1), jnp.int32),
        interpret=interpret,
    )(bucket_rows, c, r, base)


@functools.partial(jax.jit, static_argnames=("qb", "interpret"))
def occ_count_pallas_call(bucket_bytes, c, r, base, *, qb=QB, interpret=True):
    """bucket_bytes (T,32) uint8, c/r/base (T,1) int32 -> occ values (T,1).

    T must be a multiple of ``qb`` (ops.py pads).
    """
    return _occ_call(_occ_kernel_body, ETA, bucket_bytes, c, r, base,
                     qb=qb, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("qb", "interpret"))
def occ_count_packed_pallas_call(bucket_packed, c, r, base, *, qb=QB,
                                 interpret=True):
    """Baseline-layout variant: bucket_packed (T,32) uint8 holds 128
    2-bit codes per row; r is in [0, 128].  ``base`` must already carry
    the primary-row correction (ops.py folds it in)."""
    return _occ_call(_occ_packed_kernel_body, ETA, bucket_packed, c, r, base,
                     qb=qb, interpret=interpret)
