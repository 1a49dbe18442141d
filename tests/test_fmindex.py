import numpy as np
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import fmindex as fmx
from repro.data import make_reference


@pytest.fixture(scope="module")
def idx():
    return fmx.build_index(make_reference(3000, seed=3))


def brute_count(S, q):
    text = S.tobytes()
    sub = q.tobytes()
    cnt = start = 0
    while True:
        p = text.find(sub, start)
        if p < 0:
            return cnt
        cnt += 1
        start = p + 1


def backward_search(idx, q):
    k, l, s = idx.init_interval(int(q[-1]))
    for c in q[-2::-1]:
        k, l, s = idx.backward_ext(k, l, s, int(c))
        if s == 0:
            break
    return k, l, s


def test_suffix_array_sorted(idx):
    S = idx.seq
    sa = idx.sa
    # adjacent suffixes must be lexicographically ordered
    for i in range(0, len(sa) - 1, 37):
        a = S[sa[i]:sa[i] + 50].tobytes()
        b = S[sa[i + 1]:sa[i + 1] + 50].tobytes()
        assert a <= b


@pytest.mark.parametrize("packed", [True, False])
def test_suffix_array_sorts_agree_with_naive(packed):
    """Both rank-pair sorts (packed int64 key; lexsort for long
    sequences) give the naive suffix order, sentinel row first."""
    s = make_reference(700, seed=5)[:700]
    naive = sorted(range(len(s) + 1), key=lambda i: s[i:].tobytes())
    np.testing.assert_array_equal(fmx.suffix_array(s, packed=packed), naive)


def test_exact_search_counts(idx):
    rng = np.random.default_rng(0)
    S = idx.seq
    for _ in range(60):
        L = int(rng.integers(1, 24))
        p = int(rng.integers(0, len(S) - L))
        q = S[p:p + L]
        _, _, s = backward_search(idx, q)
        assert s == brute_count(S, q)


def test_bi_interval_invariant(idx):
    """s(X) == s(revcomp(X)) and l(X) == k(revcomp(X)) (Li 2012)."""
    rng = np.random.default_rng(1)
    S = idx.seq
    for _ in range(40):
        L = int(rng.integers(1, 16))
        p = int(rng.integers(0, len(S) - L))
        q = S[p:p + L]
        k, l, s = backward_search(idx, q)
        rq = (3 - q)[::-1]
        k2, l2, s2 = backward_search(idx, rq)
        assert s2 == s
        if s:
            assert k2 == l and l2 == k


def test_vectorized_occ_both_layouts(idx):
    rng = np.random.default_rng(2)
    cc = rng.integers(0, 4, size=800).astype(np.int32)
    ii = rng.integers(-1, idx.N, size=800).astype(np.int32)
    want = np.array([idx.occ(int(c), int(i)) for c, i in zip(cc, ii)])
    got_opt = np.asarray(fmx.occ_opt_v(idx.device(), jnp.asarray(cc),
                                       jnp.asarray(ii)))
    got_base = np.asarray(fmx.occ_base_v(idx.device(), jnp.asarray(cc),
                                         jnp.asarray(ii)))
    assert (got_opt == want).all()
    assert (got_base == want).all()


def test_vectorized_extension(idx):
    rng = np.random.default_rng(3)
    S = idx.seq
    ks, ls, ss, cs = [], [], [], []
    for _ in range(120):
        L = int(rng.integers(1, 10))
        p = int(rng.integers(0, len(S) - L))
        k, l, s = backward_search(idx, S[p:p + L])
        ks.append(k); ls.append(l); ss.append(s)
        cs.append(int(rng.integers(0, 5)))
    arr = lambda v: jnp.asarray(np.array(v, np.int32))
    for occ_fn in (fmx.occ_opt_v, fmx.occ_base_v):
        bk, bl, bs = fmx.backward_ext_v(idx.device(), arr(ks), arr(ls),
                                        arr(ss), arr(cs), occ_fn=occ_fn)
        fk, fl, fs = fmx.forward_ext_v(idx.device(), arr(ks), arr(ls),
                                       arr(ss), arr(cs), occ_fn=occ_fn)
        for j in range(len(ks)):
            e = idx.backward_ext(ks[j], ls[j], ss[j], cs[j])
            assert int(bs[j]) == e[2]
            if e[2]:
                assert (int(bk[j]), int(bl[j])) == (e[0], e[1])
            e = idx.forward_ext(ks[j], ls[j], ss[j], cs[j])
            assert int(fs[j]) == e[2]
            if e[2]:
                assert (int(fk[j]), int(fl[j])) == (e[0], e[1])


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(40, 300))
def test_property_random_reference(seed, n):
    """Index invariants on arbitrary references (hypothesis)."""
    rng = np.random.default_rng(seed)
    ref = rng.integers(0, 4, size=n, dtype=np.uint8)
    idx = fmx.build_index(ref)
    # C counts are consistent with the sequence
    S = idx.seq
    counts = np.bincount(S, minlength=4)
    assert idx.C[0] == 1
    for c in range(1, 4):
        assert idx.C[c] - idx.C[c - 1] == counts[c - 1]
    # occ at the end counts everything
    for c in range(4):
        assert idx.occ(c, idx.N - 1) == counts[c]
    # SAL identity on a sample of rows
    rs = rng.integers(0, idx.N, size=16)
    for i in rs:
        v, _ = idx.sa_lookup_compressed(int(i))
        assert v == idx.sa_lookup(int(i))


def test_revcomp_involution():
    rng = np.random.default_rng(5)
    x = rng.integers(0, 4, size=100, dtype=np.uint8)
    assert (fmx.revcomp(fmx.revcomp(x)) == x).all()


def test_device_view_per_default_device(idx):
    """One device view per device: the default device and the same
    device named explicitly share a view (no second upload)."""
    import jax
    view = idx.device()
    assert idx.device() is view
    with jax.default_device(jax.devices()[0]):
        assert idx.device() is view
    assert list(idx._device) == [jax.devices()[0]]
