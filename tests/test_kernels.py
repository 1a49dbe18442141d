"""Per-kernel shape/dtype sweeps asserting exact equality with the pure
oracles (interpret-mode execution of the Pallas kernel bodies)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import fmindex as fmx
from repro.core.bsw import BSWParams, bsw_extend
from repro.data import make_reference
from repro.kernels.bsw import bsw_extend_pallas
from repro.kernels.bsw.ref import bsw_ref
from repro.kernels.fmocc import backward_ext_pallas, occ_pallas


@pytest.fixture(scope="module")
def idx():
    return fmx.build_index(make_reference(4000, seed=11))


@pytest.mark.parametrize("n", [1, 7, 255, 256, 1000])
def test_fmocc_shapes(idx, n):
    rng = np.random.default_rng(n)
    cc = jnp.asarray(rng.integers(0, 4, size=n).astype(np.int32))
    ii = jnp.asarray(rng.integers(-1, idx.N, size=n).astype(np.int32))
    got = occ_pallas(idx.device(), cc, ii)
    want = fmx.occ_opt_v(idx.device(), cc, ii)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("layout,qb", [
    ("eta32", 64), ("eta32", 512), ("eta128", 64), ("eta128", 256),
])
def test_fmocc_layout_qb_grid(idx, layout, qb):
    """Every (occ layout, queries-per-grid-cell) sweep candidate returns
    the oracle's values — the engine's layout choice is throughput-only."""
    rng = np.random.default_rng(qb)
    n = 700
    cc = jnp.asarray(rng.integers(0, 4, size=n).astype(np.int32))
    ii = jnp.asarray(rng.integers(-1, idx.N, size=n).astype(np.int32))
    got = occ_pallas(idx.device(), cc, ii, layout=layout, qb=qb)
    want = fmx.occ_opt_v(idx.device(), cc, ii)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_fmocc_2d_batch(idx):
    rng = np.random.default_rng(0)
    cc = jnp.asarray(rng.integers(0, 4, size=(13, 4)).astype(np.int32))
    ii = jnp.asarray(rng.integers(-1, idx.N, size=(13, 4)).astype(np.int32))
    got = occ_pallas(idx.device(), cc, ii)
    want = fmx.occ_opt_v(idx.device(), cc, ii)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_backward_ext_pallas(idx):
    rng = np.random.default_rng(1)
    n = 200
    k = jnp.asarray(rng.integers(0, idx.N // 2, size=n).astype(np.int32))
    l = jnp.asarray(rng.integers(0, idx.N // 2, size=n).astype(np.int32))
    s = jnp.asarray(rng.integers(0, 64, size=n).astype(np.int32))
    c = jnp.asarray(rng.integers(0, 5, size=n).astype(np.int32))
    got = backward_ext_pallas(idx.device(), k, l, s, c)
    want = fmx.backward_ext_v(idx.device(), k, l, s, c)
    for g, w in zip(got, want):
        assert (np.asarray(g) == np.asarray(w)).all()


@pytest.mark.parametrize("n,maxq,maxt", [
    (1, 8, 8), (5, 40, 60), (130, 100, 120), (256, 64, 64),
])
def test_bsw_kernel_shape_sweep(n, maxq, maxt):
    rng = np.random.default_rng(n * 1000 + maxq)
    p = BSWParams()
    qs, ts, h0s = [], [], []
    for _ in range(n):
        ql = int(rng.integers(1, maxq + 1))
        tl = int(rng.integers(1, maxt + 1))
        base = rng.integers(0, 4, size=max(ql, tl) + 8).astype(np.uint8)
        q = base[:ql].copy()
        t = base[2:2 + tl].copy()
        mut = rng.random(tl) < 0.15
        t[mut] = rng.integers(0, 5, size=int(mut.sum()))
        qs.append(q)
        ts.append(t)
        h0s.append(int(rng.integers(1, 80)))
    got = bsw_extend_pallas(qs, ts, h0s, p)
    exp = [bsw_extend(q, t, h0, p) for q, t, h0 in zip(qs, ts, h0s)]
    assert got == exp


def test_bsw_kernel_vs_padded_ref_interface():
    rng = np.random.default_rng(77)
    p = BSWParams(w=7, zdrop=30)
    W, qmax, tmax = 64, 48, 56
    qlens = rng.integers(1, qmax + 1, size=W).astype(np.int32)
    tlens = rng.integers(1, tmax + 1, size=W).astype(np.int32)
    qs = rng.integers(0, 4, size=(W, qmax)).astype(np.int32)
    ts = rng.integers(0, 4, size=(W, tmax)).astype(np.int32)
    h0s = rng.integers(1, 60, size=W).astype(np.int32)
    ws = np.full(W, p.w, np.int32)
    want = bsw_ref(qs, ts, qlens, tlens, h0s, ws, p)
    got = bsw_extend_pallas(
        [qs[i, :qlens[i]].astype(np.uint8) for i in range(W)],
        [ts[i, :tlens[i]].astype(np.uint8) for i in range(W)],
        h0s.tolist(), p, ws=ws.tolist())
    got_arr = np.array([[r.score, r.qle, r.tle, r.gtle, r.gscore,
                         r.max_off] for r in got])
    assert (got_arr == want).all()
