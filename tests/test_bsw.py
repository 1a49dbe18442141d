import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.bsw import (BSWParams, bsw_extend, bsw_extend_batch,
                            sort_tasks_by_length, wasted_cell_stats)


def _mk_tasks(rng, n, maxq=150, maxt=180):
    qs, ts, h0s, ws = [], [], [], []
    for _ in range(n):
        ql = int(rng.integers(1, maxq))
        tl = int(rng.integers(1, maxt))
        if rng.random() < 0.8:
            base = rng.integers(0, 4, size=max(ql, tl) + 16).astype(np.uint8)
            off = int(rng.integers(0, 8))
            q = base[:ql].copy()
            t = base[off:off + tl].copy()
            mut = rng.random(tl) < rng.choice([0.02, 0.15, 0.5])
            t[mut] = rng.integers(0, 5, size=int(mut.sum()))
        else:
            q = rng.integers(0, 5, size=ql).astype(np.uint8)
            t = rng.integers(0, 5, size=tl).astype(np.uint8)
        qs.append(q)
        ts.append(np.asarray(t, np.uint8))
        h0s.append(int(rng.integers(1, 150)))
        ws.append(int(rng.integers(1, 110)))
    return qs, ts, h0s, ws


@pytest.mark.parametrize("cfg", [
    dict(), dict(w=3, zdrop=10), dict(w=1, zdrop=0), dict(w=5, zdrop=1),
    dict(a=2, b=3, o_del=5, e_del=2, o_ins=4, e_ins=2),
])
def test_batch_bit_identical_to_oracle(cfg):
    rng = np.random.default_rng(hash(str(cfg)) % 2**31)
    p = BSWParams(**cfg)
    qs, ts, h0s, ws = _mk_tasks(rng, 120)
    exp = [bsw_extend(q, t, h0, p, w)
           for q, t, h0, w in zip(qs, ts, h0s, ws)]
    got = bsw_extend_batch(qs, ts, h0s, p, ws=ws)
    assert exp == got


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 80), st.integers(1, 80),
       st.integers(1, 60))
def test_property_single_pair(seed, ql, tl, h0):
    """Invariants: score >= h0 is NOT guaranteed (zdrop), but score >=
    the best row max seen; qle/tle within bounds; gscore <= score +
    clip room."""
    rng = np.random.default_rng(seed)
    q = rng.integers(0, 4, size=ql).astype(np.uint8)
    t = rng.integers(0, 4, size=tl).astype(np.uint8)
    p = BSWParams()
    r = bsw_extend(q, t, h0, p)
    assert 0 <= r.qle <= ql
    assert 0 <= r.tle <= tl
    assert 0 <= r.gtle <= tl
    assert r.score >= h0        # max_ starts at h0, never decreases
    assert r.max_off >= 0
    # batch agrees
    rb = bsw_extend_batch([q], [t], [h0], p)[0]
    assert r == rb


def test_perfect_match_score():
    """A perfect continuation scores h0 + len * a (no banding effects)."""
    p = BSWParams()
    q = np.arange(40) % 4
    r = bsw_extend(q.astype(np.uint8), q.astype(np.uint8), 10, p)
    assert r.score == 10 + 40 * p.a
    assert r.gscore == 10 + 40 * p.a
    assert r.qle == 40 and r.tle == 40


def test_sorting_reduces_wasted_cells():
    rng = np.random.default_rng(4)
    qlens = rng.integers(10, 200, size=512)
    tlens = rng.integers(10, 250, size=512)
    order = sort_tasks_by_length(qlens, tlens)
    u_sorted, t_sorted = wasted_cell_stats(qlens, tlens, order, block=64)
    u_raw, t_raw = wasted_cell_stats(qlens, tlens, np.arange(512), block=64)
    assert u_sorted == u_raw                      # same useful work
    assert t_sorted < t_raw                       # fewer computed cells
