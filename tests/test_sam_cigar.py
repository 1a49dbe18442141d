"""The row-at-a-time CIGAR DP against the cell-at-a-time one it replaced.

``scalar_global_align_cigar`` is the scalar banded global affine DP that
``core/sam.py`` ran before its fill was vectorised along the band's rows,
kept verbatim as the oracle: the shipped function has to return the same
``(score, cigar)`` on every case, ties in the traceback broken the same
way.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.bsw import BSWParams
from repro.core.sam import global_align_cigar


def scalar_global_align_cigar(q: np.ndarray, t: np.ndarray, w: int,
                              p: BSWParams) -> tuple[int, list[tuple[int, str]]]:
    """Banded global affine-gap alignment with traceback -> (score, cigar).

    q aligned fully to t; band of half-width w around the diagonal scaled
    to the length difference (as ksw_global does).
    """
    n, m = len(q), len(t)
    if n == 0:
        return (-p.o_del - p.e_del * m if m else 0), ([(m, "D")] if m else [])
    if m == 0:
        return -p.o_ins - p.e_ins * n, [(n, "I")]
    mat = p.matrix()
    w = max(w, abs(n - m) + 3)
    NEG = -(1 << 28)
    H = np.full((n + 1, m + 1), NEG, np.int64)
    E = np.full((n + 1, m + 1), NEG, np.int64)   # gap in query (deletion, consume t)
    F = np.full((n + 1, m + 1), NEG, np.int64)   # gap in target (insertion, consume q)
    H[0, 0] = 0
    for j in range(1, min(m, w) + 1):
        E[0, j] = -(p.o_del + p.e_del * j)
        H[0, j] = E[0, j]
    for i in range(1, min(n, w) + 1):
        F[i, 0] = -(p.o_ins + p.e_ins * i)
        H[i, 0] = F[i, 0]
    for i in range(1, n + 1):
        jlo = max(1, i - w)
        jhi = min(m, i + w)
        for j in range(jlo, jhi + 1):
            E[i, j] = max(E[i, j - 1] - p.e_del, H[i, j - 1] - p.o_del - p.e_del)
            F[i, j] = max(F[i - 1, j] - p.e_ins, H[i - 1, j] - p.o_ins - p.e_ins)
            diag = H[i - 1, j - 1] + mat[int(q[i - 1]), int(t[j - 1])]
            H[i, j] = max(diag, E[i, j], F[i, j])
    # traceback
    i, j = n, m
    ops: list[str] = []
    state = "H"
    while i > 0 or j > 0:
        if state == "H":
            if i > 0 and j > 0 and H[i, j] == (
                    H[i - 1, j - 1] + mat[int(q[i - 1]), int(t[j - 1])]):
                ops.append("M")
                i -= 1
                j -= 1
            elif j > 0 and H[i, j] == E[i, j]:
                state = "E"
            elif i > 0 and H[i, j] == F[i, j]:
                state = "F"
            else:  # out-of-band corner: force remaining as gaps
                if i == 0:
                    ops.append("D"); j -= 1
                elif j == 0:
                    ops.append("I"); i -= 1
                else:
                    ops.append("M"); i -= 1; j -= 1
        elif state == "E":
            ops.append("D")
            if E[i, j] == H[i, j - 1] - p.o_del - p.e_del:
                state = "H"
            j -= 1
        else:
            ops.append("I")
            if F[i, j] == H[i - 1, j] - p.o_ins - p.e_ins:
                state = "H"
            i -= 1
    ops.reverse()
    cigar: list[tuple[int, str]] = []
    for op in ops:
        if cigar and cigar[-1][1] == op:
            cigar[-1] = (cigar[-1][0] + 1, op)
        else:
            cigar.append((1, op))
    return int(H[n, m]), cigar


def _seq(rng, n, alphabet=4):
    return rng.integers(0, alphabet, n).astype(np.uint8)


def _mutate(rng, q, n_ins=0, n_del=0, at=None, sub=0.0):
    """``q`` with ``sub`` substitutions per base, then ``n_ins`` inserted
    and ``n_del`` deleted bases at ``at`` (default: the middle)."""
    t = q.copy()
    hit = rng.random(len(t)) < sub
    t[hit] = (t[hit] + rng.integers(1, 4, int(hit.sum()))) % 4
    at = len(t) // 2 if at is None else at
    return np.concatenate([t[:at], _seq(rng, n_ins), t[at + n_del:]])


def _check(q, t, w, p=BSWParams()):
    q = np.asarray(q, np.uint8)
    t = np.asarray(t, np.uint8)
    want = scalar_global_align_cigar(q, t, w, p)
    got = global_align_cigar(q, t, w, p)
    assert got == want
    assert type(got[0]) is int
    return got


_P_ASYM = BSWParams(o_del=4, e_del=2, o_ins=7, e_ins=1)
_RNG = np.random.default_rng(7)
_Q100 = _seq(_RNG, 100)
_Q40 = _seq(_RNG, 40)


@pytest.mark.parametrize("q, t, w", [
    ([], [], 100),                                   # both empty
    ([], [0, 1, 2], 100),                            # empty query
    ([0, 1, 2], [], 100),                            # empty target
    ([2], [0, 1, 2, 3, 2], 100),                     # a single row
    ([1], [1], 0),                                   # one cell
    (_Q40, _mutate(_RNG, _Q40, sub=0.1), 0),         # w = 0, widened to 3
    (_Q40, _mutate(_RNG, _Q40, n_del=12), 2),        # w < |n - m|
    (_Q40, _mutate(_RNG, _Q40, n_ins=9), 5),         # w < |n - m|, n < m
    (_Q40, _mutate(_RNG, _Q40, sub=0.05), 500),      # band wider than the matrix
    (_Q100, _mutate(_RNG, _Q100, n_ins=1, sub=0.01), 100),   # a 100 bp read
    ([4, 4, 0, 1, 4, 2, 3, 4], [0, 4, 1, 4, 2, 3, 3], 100),  # N bases
    (np.where(_RNG.random(60) < 0.2, 4, _seq(_RNG, 60)),
     _seq(_RNG, 63), 10),                            # N in the query only
])
def test_matches_the_scalar_dp(q, t, w):
    _check(q, t, w)


@pytest.mark.parametrize("n_ins, n_del, w", [
    (0, 17, 14),        # deletion as long as the widened band allows
    (17, 0, 14),        # the same for an insertion
    (0, 30, 10),        # band widened past the deletion
    (30, 0, 10),
    (6, 0, 6),          # insertion at the band's edge
    (0, 6, 6),
])
@pytest.mark.parametrize("at", [0, 20, 59])
def test_long_gaps_at_the_band_edge(n_ins, n_del, w, at):
    rng = np.random.default_rng(n_ins * 100 + n_del + at)
    q = _seq(rng, 60)
    t = _mutate(rng, q, n_ins=n_ins, n_del=n_del, at=at)
    # an insertion in the read is a deletion from the target's view
    _check(q, t, w)
    _check(t, q, w)


@pytest.mark.parametrize("unit", ["A", "AC", "ACG", "AAAC"])
@pytest.mark.parametrize("w", [3, 100])
def test_low_complexity_ties(unit, w):
    code = {"A": 0, "C": 1, "G": 2, "T": 3}
    rep = [code[c] for c in unit]
    q = (rep * 40)[:50]
    for t in ((rep * 40)[:53], (rep * 40)[:47], (rep * 40)[1:51]):
        _check(q, t, w)


@pytest.mark.parametrize("p", [
    _P_ASYM,
    BSWParams(o_del=1, e_del=3, o_ins=0, e_ins=2),
    BSWParams(a=2, b=3, o_del=0, e_del=1, o_ins=9, e_ins=4),
])
@pytest.mark.parametrize("n_ins, n_del", [(0, 0), (5, 0), (0, 5), (3, 8)])
def test_asymmetric_gap_penalties(p, n_ins, n_del):
    rng = np.random.default_rng(n_ins * 10 + n_del)
    q = _seq(rng, 70)
    t = _mutate(rng, q, n_ins=n_ins, n_del=n_del, sub=0.03)
    for w in (0, 4, 100):
        _check(q, t, w, p)
        _check(t, q, w, p)


@pytest.mark.parametrize("seed", range(8))
def test_random_draws(seed):
    """50 random (n, m, w, scoring) draws per seed, 400 in all."""
    rng = np.random.default_rng(1000 + seed)
    for k in range(50):
        n, m = (int(x) for x in rng.integers(0, 48, 2))
        w = int(rng.integers(0, 40))
        q = _seq(rng, n, int(rng.integers(1, 6)))      # some draws with N
        t = (_seq(rng, m, 5) if k % 3 else
             _mutate(rng, q, n_ins=int(rng.integers(0, 6)),
                     n_del=min(n, int(rng.integers(0, 6))), sub=0.1)[:48])
        p = _P_ASYM if k % 2 else BSWParams()
        _check(q, t, w, p)
