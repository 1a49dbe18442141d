import functools

import numpy as np
import pytest

from repro.core import fmindex as fmx
from repro.core import smem as sm
from repro.core.fmindex import occ_base_v, occ_opt_np, occ_opt_v
from repro.data import make_reference, simulate_reads
from repro.kernels.fmocc.ops import make_occ_fn


@pytest.fixture(scope="module")
def setup():
    ref = make_reference(12000, seed=5)
    idx = fmx.build_index(ref)
    reads, _ = simulate_reads(ref, 24, 101, seed=2)
    return idx, reads


def test_smem1_matches_definition(setup):
    idx, reads = setup
    for r in range(8):
        q = reads[r]
        brute = sm.brute_smems(idx, q)
        got = []
        x = 0
        while x < len(q):
            if q[x] < 4:
                ms, x = sm.smem1(idx, q, x, 1)
                got.extend((m[3], m[4]) for m in ms)
            else:
                x += 1
        assert sorted(set(got)) == brute


def test_smem_interval_sizes_are_occurrence_counts(setup):
    idx, reads = setup
    text = idx.seq.tobytes()
    q = reads[0]
    ms, _ = sm.smem1(idx, q, 40, 1)
    for (k, l, s, qb, qe) in ms:
        sub = q[qb:qe].tobytes()
        cnt = start = 0
        while True:
            p = text.find(sub, start)
            if p < 0:
                break
            cnt += 1
            start = p + 1
        assert cnt == s


def test_batched_identical_to_oracle_both_layouts(setup):
    idx, reads = setup
    opt = sm.MemOptions()
    lens = np.full(len(reads), reads.shape[1], np.int64)
    oracle = [sm.collect_smems(idx, reads[r], opt)
              for r in range(len(reads))]
    for occ_fn in (occ_opt_v, occ_base_v):
        got = sm.collect_smems_batch(idx, reads, lens, opt, occ_fn=occ_fn)
        assert got == oracle


def test_reads_with_ambiguous_bases(setup):
    idx, _ = setup
    rng = np.random.default_rng(9)
    reads = rng.integers(0, 4, size=(6, 80)).astype(np.uint8)
    reads[:, ::17] = 4                    # plant Ns
    opt = sm.MemOptions()
    lens = np.full(6, 80, np.int64)
    oracle = [sm.collect_smems(idx, reads[r], opt) for r in range(6)]
    got = sm.collect_smems_batch(idx, reads, lens, opt)
    assert got == oracle


# ---------------------------------------------------------------------
# device loops (jnp occ, and the Pallas kernel in interpret mode) against
# the host's NumPy rounds: the same SMEMs, intervals and order
# ---------------------------------------------------------------------

L_DEV = 48          # one read width, so each task bucket compiles once

DEVICE_OCC = {
    "jnp": occ_opt_v,
    "pallas": make_occ_fn("eta32", 256, interpret=True),
}


@functools.lru_cache(maxsize=None)
def _dev_world(seed: int):
    """An index with repeats, and reads of width L_DEV: simulated ones
    and random ones, with N bases, most of them shorter than the width."""
    ref = make_reference(4000 + 1000 * seed, seed=seed)
    idx = fmx.build_index(ref)
    sim, _ = simulate_reads(ref, 24, L_DEV, seed=seed + 100)
    rng = np.random.default_rng(seed)
    rnd = rng.integers(0, 4, size=(8, L_DEV)).astype(np.uint8)
    reads = np.concatenate([sim, rnd]).astype(np.uint8)
    reads[rng.random(reads.shape) < 0.03] = 4
    lens = rng.integers(L_DEV // 3, L_DEV + 1, len(reads)).astype(np.int64)
    lens[:4] = L_DEV
    return idx, reads, lens


def _dev_tasks(seed: int, T: int, reads, lens):
    rng = np.random.default_rng(1000 * seed + T)
    tr = rng.integers(0, len(reads), T)
    tx = rng.integers(0, L_DEV + 2, T)           # some start past the end
    mi = rng.choice([1, 1, 2, 3, 5, 10], T)
    return tr, tx, mi


@pytest.mark.parametrize("occ", sorted(DEVICE_OCC))
@pytest.mark.parametrize("T", [1, 31, 32, 33, 70])
@pytest.mark.parametrize("seed", [0, 1])
def test_smem1_device_loop_matches_numpy(seed, T, occ):
    idx, reads, lens = _dev_world(seed)
    tr, tx, mi = _dev_tasks(seed, T, reads, lens)
    want = sm.smem1_batch(idx, reads, lens, tr, tx, mi, occ_fn=occ_opt_np)
    got = sm.smem1_batch(idx, reads, lens, tr, tx, mi,
                         occ_fn=DEVICE_OCC[occ])
    assert want.n.sum() > 0
    for f in ("k", "l", "s", "qbeg", "qend", "n", "ret"):
        a, b = getattr(want, f), getattr(got, f)
        assert a.shape == b.shape and np.array_equal(a, b), f


@pytest.mark.parametrize("unit", ["A", "AC", "ACGTT"])
def test_smem1_device_loop_with_long_forward_lists(unit):
    """A tandem-repeat index whose interval shrinks at almost every base,
    so forward lists fill most of the P slots; the backward rounds over
    all of them give the NumPy path's SMEMs."""
    rng = np.random.default_rng(len(unit))
    codes = np.array(["ACGT".index(b) for b in unit], np.uint8)
    ref = np.tile(codes, 3000 // len(codes))
    ref[rng.random(len(ref)) < 0.01] = rng.integers(0, 4)
    idx = fmx.build_index(ref)
    starts = rng.integers(0, len(ref) - L_DEV, 40)
    reads = np.stack([ref[p:p + L_DEV] for p in starts]).astype(np.uint8)
    lens = np.full(len(reads), L_DEV, np.int64)
    tr = np.arange(len(reads))
    tx = rng.integers(0, L_DEV // 2, len(reads))
    mi = np.ones(len(reads), np.int64)
    want = sm.smem1_batch(idx, reads, lens, tr, tx, mi, occ_fn=occ_opt_np)
    got = sm.smem1_batch(idx, reads, lens, tr, tx, mi, occ_fn=occ_opt_v)
    assert want.n.sum() > 0
    for f in ("k", "l", "s", "qbeg", "qend", "n", "ret"):
        assert np.array_equal(getattr(want, f), getattr(got, f)), f


@pytest.mark.parametrize("occ", sorted(DEVICE_OCC))
@pytest.mark.parametrize("T", [1, 32, 33, 70])
@pytest.mark.parametrize("seed", [0, 1])
def test_seed_strategy1_device_loop_matches_numpy(seed, T, occ):
    idx, reads, lens = _dev_world(seed)
    tr, tx, _ = _dev_tasks(seed, T, reads, lens)
    tx = np.minimum(tx, 20)           # leave room for a min_len match
    for min_len, max_intv in ((8, 20), (19, 20)):
        want = sm.seed_strategy1_batch(idx, reads, lens, tr, tx, min_len,
                                       max_intv, occ_fn=occ_opt_np)
        got = sm.seed_strategy1_batch(idx, reads, lens, tr, tx, min_len,
                                      max_intv, occ_fn=DEVICE_OCC[occ])
        for a, b in zip(want, got):
            assert a.dtype == b.dtype and np.array_equal(a, b)
    assert want[1].any() or T == 1


@pytest.mark.parametrize("occ", sorted(DEVICE_OCC))
@pytest.mark.parametrize("seed", [0, 1])
def test_collect_smems_device_loop_matches_numpy(seed, occ):
    idx, reads, lens = _dev_world(seed)
    opt = sm.MemOptions(min_seed_len=10, split_width=20, max_mem_intv=20)
    want = sm.collect_smems_batch(idx, reads, lens, opt, occ_fn=occ_opt_np)
    got = sm.collect_smems_batch(idx, reads, lens, opt,
                                 occ_fn=DEVICE_OCC[occ])
    assert got == want and sum(map(len, want)) > 0
    oracle = [sm.collect_smems(idx, reads[r, :lens[r]], opt)
              for r in range(len(reads))]
    assert got == oracle


def test_device_loop_is_one_dispatch_per_call(setup):
    """A call is one device loop however many rounds it runs; the rounds
    still land on ``smem_rounds``, the same count the host rounds give."""
    from repro import obs
    idx, reads = setup
    lens = np.full(len(reads), reads.shape[1], np.int64)
    tr = np.arange(len(reads))
    tx = np.zeros(len(reads), np.int64)
    counts = {}
    for name, fn in (("numpy", occ_opt_np), ("jnp", occ_opt_v)):
        reg = obs.MetricsRegistry()
        with obs.activate(reg):
            sm.smem1_batch(idx, reads, lens, tr, tx, np.ones_like(tx),
                           occ_fn=fn)
            sm.seed_strategy1_batch(idx, reads, lens, tr, tx, 19, 20,
                                    occ_fn=fn)
        counts[name] = reg.snapshot()
    assert counts["numpy"]["smem_rounds"] > reads.shape[1]
    assert counts["jnp"]["smem_rounds"] == counts["numpy"]["smem_rounds"]
    assert counts["jnp"]["smem_occ_dispatches"] == 2
    assert "smem_occ_dispatches" not in counts["numpy"]
