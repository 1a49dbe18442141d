"""Pallas TPU kernel: inter-task banded Smith-Waterman (paper §5.3).

TPU mapping of the paper's AVX512 inter-task vectorization:

* one grid cell processes a block of LANES=128 sequence pairs, the
  paper's task lanes (AVX512 gives 64 8-bit lanes).  On the TPU each task
  is one sublane row of the (8, 128) VREG tiles and the query columns run
  along the 128 hardware lanes, so one DP row of a block is a stack of
  whole tiles;
* sequences arrive SoA (``(LANES, qmax)`` / ``(LANES, tmax)``) so each DP
  row touches contiguous VMEM — the paper's AoS->SoA conversion (§5.3.3);
* per-task scalars (lengths, h0, band, the running max/band state) are
  ``(LANES, 1)`` columns that broadcast along the lanes; the target base
  of row i is a one-hot masked reduction over ``ts`` (the kernel has no
  dynamic lane slice), and the loop-carried ``alive`` flag is int32
  (Mosaic cannot carry boolean vectors through the row loop);
* both DP rows (H and E) live in VMEM scratch for the whole row loop: the
  working set per block is LANES x (qmax+1) x 2 x 4B ≈ 0.5 MB at qmax=512,
  far under the ~16 MB VMEM budget, so BlockSpec keeps everything resident;
* the scalar in-row F recurrence is replaced by a Hillis-Steele prefix max
  (max-plus algebra) — log2(qmax) vectorized steps instead of a serial
  carry, the TPU equivalent of the paper's in-register dependency chain;
* band adjustment / z-drop / early exit are lane-masked (paper §5.4(d):
  "mask and cmp instructions maintain correct values for aborted pairs").

The DP math is ``repro.core.bsw.bsw_row_step`` — the *same* traced code as
the jnp batch reference, so kernel == reference == scalar oracle exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bsw import bsw_init_state, bsw_result, bsw_row_step

LANES = 128


def _bsw_kernel_body(qs_ref, ts_ref, qlens_ref, tlens_ref, h0s_ref, ws_ref,
                     out_ref, *, a, b, o_del, e_del, o_ins, e_ins, zdrop,
                     qmax, tmax):
    qs = qs_ref[...]
    ts = ts_ref[...]
    qlens = qlens_ref[...]                            # (LANES, 1) columns
    tlens = tlens_ref[...]
    h0s = h0s_ref[...]
    ws = ws_ref[...]

    state = bsw_init_state(qlens, h0s, o_ins + e_ins, e_ins, qmax)
    jt = jax.lax.broadcasted_iota(jnp.int32, (1, tmax), 1)

    def row(i, st):
        # target base of row i: a one-hot reduction (no dynamic lane slice)
        trow = jnp.sum(jnp.where(jt == i, ts, 0), axis=1, keepdims=True)
        return bsw_row_step(i, st, qs, trow, qlens, tlens, h0s, ws,
                            a, b, o_del, e_del, o_ins, e_ins, zdrop, qmax)

    out_ref[...] = bsw_result(jax.lax.fori_loop(0, tmax, row, state))


@functools.partial(jax.jit, static_argnames=(
    "a", "b", "o_del", "e_del", "o_ins", "e_ins", "zdrop", "qmax", "tmax",
    "interpret"))
def bsw_pallas_call(qs, ts, qlens, tlens, h0s, ws, *, a, b, o_del, e_del,
                    o_ins, e_ins, zdrop, qmax, tmax, interpret=True):
    """qs (W,qmax) / ts (W,tmax) int32 (pad code 4); qlens/tlens/h0s/ws
    (W, 1) int32 columns; W % LANES == 0.

    Returns (W, 6) int32: score, qle, tle, gtle, gscore, max_off.
    """
    W = qs.shape[0]
    assert W % LANES == 0, "pad the task batch to a multiple of LANES"
    grid = (W // LANES,)
    body = functools.partial(
        _bsw_kernel_body, a=a, b=b, o_del=o_del, e_del=e_del, o_ins=o_ins,
        e_ins=e_ins, zdrop=zdrop, qmax=qmax, tmax=tmax)
    col = pl.BlockSpec((LANES, 1), lambda g: (g, 0))
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((LANES, qmax), lambda g: (g, 0)),
            pl.BlockSpec((LANES, tmax), lambda g: (g, 0)),
            col, col, col, col,
        ],
        out_specs=pl.BlockSpec((LANES, 6), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((W, 6), jnp.int32),
        interpret=interpret,
    )(qs, ts, qlens, tlens, h0s, ws)
