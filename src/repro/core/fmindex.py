"""FM-index over R + revcomp(R) with the paper's two occupancy-table layouts.

This module implements the index substrate for the three BWA-MEM kernels:

* ``build_index`` constructs the suffix array, BWT, cumulative counts ``C``,
  and BOTH occupancy ("O_c") layouts studied by the paper:

  - **optimized** (paper §4.4): bucket size eta=32, one *byte* per base, one
    64-byte (cache-line / VREG-row) bucket per entry.  occ(c, i) is a
    byte-compare + popcount — on TPU a VPU compare + reduce.
  - **baseline** (original BWA-MEM): eta=128, 2-bit packed bases; occ(c, i)
    requires unpack + bit manipulation (the ">4x instructions" the paper
    measures in Table 4).

* The suffix array is kept BOTH uncompressed (paper §4.5, the 183x SAL fix)
  and value-sampled with factor 32 (original BWA-MEM SAL baseline).

All device-side integers are int32 (the paper itself uses 4-byte counts,
§4.4); references handled in this container are far below 2^31 bases.

Index convention (0-based, self-contained — see DESIGN.md §2):
  S = R · revcomp(R), length 2n; the sentinel ``$`` is virtual: the suffix
  array is built over S+'$' (length N=2n+1) and row ``primary`` is the row
  whose BWT char is '$'.  The BWT is stored as bytes with value 4 at
  ``primary`` so that compares against c in {0..3} never match it.

  Backward extension of bi-interval (k, l, s) by base c:
      k_c = C[c] + Occ(c, k-1)
      s_c = Occ(c, k+s-1) - Occ(c, k-1)
      l_3 = l + [primary in [k, k+s)] ;  l_2 = l_3 + s_3 ;
      l_1 = l_2 + s_2 ;  l_0 = l_1 + s_1
  (l-order T,G,C,A because prepending c to X appends complement(c) to
  revcomp(X); see Li 2012.)
"""

from __future__ import annotations

import dataclasses
import threading
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

# Base codes. 0=A 1=C 2=G 3=T; 4 = sentinel marker in BWT bytes; 5 = pad.
SENTINEL = 4
PAD = 5

OPT_ETA = 32      # paper's optimized bucket size (one cache line / VREG row)
BASE_ETA = 128    # original BWA-MEM bucket size (2-bit packed)
SA_SAMPLE = 32    # suffix-array sampling of the baseline compressed SA

I32 = jnp.int32

#: Serializes FMIndex.device() lazy builds (see that method).
_DEVICE_LOCK = threading.Lock()


def revcomp(codes: np.ndarray) -> np.ndarray:
    """Reverse complement of a 0..3 coded sequence (3 - c swaps A<->T, C<->G)."""
    return (3 - codes[::-1]).astype(codes.dtype)


def suffix_array(s: np.ndarray, *, packed: bool | None = None) -> np.ndarray:
    """Suffix array by prefix doubling (O(n log^2 n), one numpy sort per
    round).  The rank pair (rank[i], rank[i+k]) is packed into one int64
    key, which sorts about 5x faster than a two-key lexsort, while
    (n+1)^2 fits an int64 (n < 3.03e9, whole human chromosomes); longer
    sequences (a whole-genome forward+reverse sequence) sort the pair
    with a lexsort.  ``packed`` forces either sort (tests).

    The caller passes the sequence WITHOUT sentinel; we treat the virtual
    sentinel as smaller than everything by ranking positions past the end
    as -1.  Returned SA has length len(s)+1 and SA[0] == len(s) ($ row).
    """
    s = np.asarray(s, dtype=np.int64)
    n = len(s) + 1  # +1 for the virtual sentinel position at index len(s)
    if packed is None:
        packed = (n + 1) ** 2 < 2 ** 63
    rank = np.full(n, -1, dtype=np.int64)
    rank[:-1] = s
    k = 1
    while True:
        key2 = np.full(n, -1, dtype=np.int64)
        if k < n:
            key2[: n - k] = rank[k:]
        if packed:
            key = (rank + 1) * (n + 1) + (key2 + 1)    # ranks are in [-1, n)
            sa = np.argsort(key)    # ties reorder freely: the last round has none
            key = key[sa]
            diff = key[1:] != key[:-1]
        else:
            sa = np.lexsort((key2, rank))
            diff = ((rank[sa[1:]] != rank[sa[:-1]])
                    | (key2[sa[1:]] != key2[sa[:-1]]))
        new = np.empty(n, dtype=np.int64)
        new[sa] = np.concatenate(([0], np.cumsum(diff)))
        rank = new
        if rank[sa[-1]] == n - 1:
            return sa
        k *= 2


class FMArrays(NamedTuple):
    """Device-side (jnp) view of the index used by the jitted kernels."""
    # optimized occ layout (eta=32, one byte per base, 64B-aligned buckets)
    occ32_counts: jnp.ndarray   # (nb32, 4) int32 — counts up to bucket start
    occ32_bytes: jnp.ndarray    # (nb32, 32) uint8 — raw BWT bytes of bucket
    # baseline occ layout (eta=128, 2-bit packed)
    occ128_counts: jnp.ndarray  # (nb128, 4) int32
    occ128_packed: jnp.ndarray  # (nb128, 32) uint8 — 4 bases per byte, LSB first
    C: jnp.ndarray              # (4,) int32 cumulative counts (incl. +1 for $ row)
    primary: jnp.ndarray        # () int32 — BWT row holding the sentinel
    sa: jnp.ndarray             # (N,) int32 — UNCOMPRESSED suffix array (opt SAL)
    sa_sampled: jnp.ndarray     # (ceil(N/32),) int32 — sampled SA (baseline SAL)
    bwt: jnp.ndarray            # (N,) uint8 — BWT bytes (0..3, 4 at primary)
    n_ref: jnp.ndarray          # () int32 — |R|
    N: jnp.ndarray              # () int32 — 2|R|+1


@dataclasses.dataclass
class FMIndex:
    """Host-side index (numpy) + lazily-built device view."""
    n_ref: int
    N: int                      # 2*n_ref + 1 (includes virtual sentinel row)
    seq: np.ndarray             # S = R+revcomp(R), (2n,) uint8
    sa: np.ndarray              # (N,) int64
    bwt: np.ndarray             # (N,) uint8, value 4 at primary
    primary: int
    C: np.ndarray               # (4,) int64
    occ32_counts: np.ndarray
    occ32_bytes: np.ndarray
    occ128_counts: np.ndarray
    occ128_packed: np.ndarray
    sa_sampled: np.ndarray
    _occ_prefix: np.ndarray | None = None
    _device: dict = dataclasses.field(default_factory=dict)  # dev -> view

    # ---------------- host-side scalar occ (oracle) ----------------
    def occ(self, c: int, i: int) -> int:
        """Occ(c, i) = # of c in BWT[0..i]; i may be -1. Oracle path (numpy)."""
        if i < 0:
            return 0
        return int(self._occ_prefix[i + 1, c])

    def backward_ext(self, k: int, l: int, s: int, c: int):
        """Bi-interval of cX given bi-interval (k,l,s) of X. Returns (k,l,s)."""
        if c > 3:
            return (k, l, 0)
        ks, ss = [], []
        for cc in range(4):
            o1 = self.occ(cc, k - 1)
            o2 = self.occ(cc, k + s - 1)
            ks.append(int(self.C[cc]) + o1)
            ss.append(o2 - o1)
        sent = 1 if (k <= self.primary < k + s) else 0
        l3 = l + sent
        l2 = l3 + ss[3]
        l1 = l2 + ss[2]
        l0 = l1 + ss[1]
        ls = [l0, l1, l2, l3]
        return (ks[c], ls[c], ss[c])

    def forward_ext(self, k: int, l: int, s: int, c: int):
        if c > 3:
            return (k, l, 0)
        l2, k2, s2 = self.backward_ext(l, k, s, 3 - c)
        return (k2, l2, s2)

    def init_interval(self, c: int):
        """Bi-interval of the single-base string c."""
        if c > 3:
            return (0, 0, 0)
        cnt = int(self.C[c + 1] - self.C[c]) if c < 3 else int(self.N - self.C[3])
        return (int(self.C[c]), int(self.C[3 - c]), cnt)

    def sa_lookup(self, i: int) -> int:
        """Optimized SAL (paper §4.5): one uncompressed-array load."""
        return int(self.sa[i])

    def sa_lookup_compressed(self, i: int) -> tuple[int, int]:
        """Baseline SAL: walk LF-mapping until a sampled row. Returns (value, steps)."""
        t = 0
        j = i
        while j % SA_SAMPLE != 0:
            # LF(j) = C[B[j]] + Occ(B[j], j-1); LF of the primary row is row 0.
            b = int(self.bwt[j])
            if b == SENTINEL:
                return (t % self.N, t)  # SA[primary] = 0 -> value = t
            j = int(self.C[b]) + self.occ(b, j - 1)
            t += 1
        return ((int(self.sa_sampled[j // SA_SAMPLE]) + t) % self.N, t)

    def device(self) -> FMArrays:
        """Device view on this thread's default device: a run that gives
        each worker thread its own chip (``jax.default_device``, as
        ``repro.dist.run`` does) gets one copy of the index per chip."""
        dev = jax.config.jax_default_device or jax.devices()[0]
        view = self._device.get(dev)
        if view is not None:
            return view
        # one lock for all indexes: the build is rare (once per index and
        # device) and concurrent aligner calls sharing one index
        # (repro.serve) must not duplicate the host->device transfer
        with _DEVICE_LOCK:
            view = self._device.get(dev)
            if view is not None:
                return view
            view = self._device[dev] = FMArrays(
                occ32_counts=jnp.asarray(self.occ32_counts, dtype=I32),
                occ32_bytes=jnp.asarray(self.occ32_bytes),
                occ128_counts=jnp.asarray(self.occ128_counts, dtype=I32),
                occ128_packed=jnp.asarray(self.occ128_packed),
                C=jnp.asarray(self.C, dtype=I32),
                primary=jnp.asarray(self.primary, dtype=I32),
                sa=jnp.asarray(self.sa, dtype=I32),
                sa_sampled=jnp.asarray(self.sa_sampled, dtype=I32),
                bwt=jnp.asarray(self.bwt),
                n_ref=jnp.asarray(self.n_ref, dtype=I32),
                N=jnp.asarray(self.N, dtype=I32),
            )
        return view


# Fields persisted by the on-disk index bundle (repro.io.store); the occ
# prefix oracle and the lazy device view are derived state, rebuilt on load.
PERSIST_ARRAYS = ("seq", "sa", "bwt", "C", "occ32_counts", "occ32_bytes",
                  "occ128_counts", "occ128_packed", "sa_sampled")
PERSIST_SCALARS = ("n_ref", "N", "primary")


def occ_prefix_from_bwt(bwt: np.ndarray) -> np.ndarray:
    """(N+1, 4) Occ prefix table from the BWT bytes (the host oracle).

    Shared by ``build_index`` and ``repro.io.store.load_index`` so a
    loaded index is byte-identical to a freshly built one.
    """
    occ_prefix = np.zeros((len(bwt) + 1, 4), dtype=np.int64)
    for c in range(4):
        occ_prefix[1:, c] = np.cumsum(bwt == c)
    return occ_prefix


def index_from_arrays(arrays: dict, scalars: dict) -> FMIndex:
    """Reassemble an ``FMIndex`` from its persisted arrays + scalars
    (see ``PERSIST_ARRAYS``/``PERSIST_SCALARS``), rebuilding derived
    state."""
    return FMIndex(**{k: int(scalars[k]) for k in PERSIST_SCALARS},
                   **{k: np.asarray(arrays[k]) for k in PERSIST_ARRAYS},
                   _occ_prefix=occ_prefix_from_bwt(np.asarray(arrays["bwt"])))


def build_index(ref: np.ndarray) -> FMIndex:
    """Build the full FM-index over S = ref + revcomp(ref).

    ``ref``: (n,) uint8 codes in 0..3 (ambiguous bases must be pre-replaced,
    as BWA does when building its index).
    """
    ref = np.asarray(ref, dtype=np.uint8)
    assert ref.ndim == 1 and ref.size > 0 and int(ref.max(initial=0)) <= 3
    n = len(ref)
    S = np.concatenate([ref, revcomp(ref)])          # length 2n
    sa = suffix_array(S)                             # length N = 2n+1
    N = 2 * n + 1

    # BWT: B[i] = S[sa[i]-1]; the row with sa[i]==0 gets the sentinel marker.
    bwt = np.empty(N, dtype=np.uint8)
    prev_idx = sa - 1
    mask = prev_idx >= 0
    bwt[mask] = S[prev_idx[mask]]
    primary = int(np.nonzero(~mask)[0][0])
    bwt[primary] = SENTINEL

    counts = np.bincount(S, minlength=4).astype(np.int64)
    C = np.zeros(4, dtype=np.int64)
    C[0] = 1  # the $ row
    for c in range(1, 4):
        C[c] = C[c - 1] + counts[c - 1]

    # ---- occ prefix table (host oracle only; O(N) memory x4) ----
    occ_prefix = occ_prefix_from_bwt(bwt)

    # ---- optimized layout: eta=32, one byte per base ----
    nb32 = N // OPT_ETA + 1
    padded32 = np.full(nb32 * OPT_ETA, PAD, dtype=np.uint8)
    padded32[:N] = bwt
    occ32_bytes = padded32.reshape(nb32, OPT_ETA)
    occ32_counts = occ_prefix[: nb32 * OPT_ETA : OPT_ETA, :].astype(np.int32)

    # ---- baseline layout: eta=128, 2-bit packed ----
    nb128 = N // BASE_ETA + 1
    padded128 = np.zeros(nb128 * BASE_ETA, dtype=np.uint8)
    padded128[:N] = bwt
    padded128[padded128 > 3] = 0  # sentinel/pad packed as 0; corrected in occ query
    codes = padded128.reshape(nb128, BASE_ETA)
    # 4 bases per byte, LSB-first: byte j holds codes [4j..4j+3]
    b0, b1, b2, b3 = (codes[:, i::4] for i in range(4))
    occ128_packed = (b0 | (b1 << 2) | (b2 << 4) | (b3 << 6)).astype(np.uint8)
    occ128_counts = occ_prefix[: nb128 * BASE_ETA : BASE_ETA, :].astype(np.int32)

    sa_sampled = sa[::SA_SAMPLE].copy()

    return FMIndex(
        n_ref=n, N=N, seq=S, sa=sa, bwt=bwt, primary=primary, C=C,
        occ32_counts=occ32_counts, occ32_bytes=occ32_bytes,
        occ128_counts=occ128_counts, occ128_packed=occ128_packed,
        sa_sampled=sa_sampled, _occ_prefix=occ_prefix,
    )


# ====================================================================
# Vectorized (jnp) occ + extension — shared by SMEM/SAL batched kernels
# ====================================================================

def occ_opt_v(fm: FMArrays, c: jnp.ndarray, i: jnp.ndarray) -> jnp.ndarray:
    """Vectorized Occ(c, i) with the optimized eta=32 byte layout.

    c: (...,) int32 in 0..3 ; i: (...,) int32 (may be -1).
    This is the TPU analogue of the paper's AVX2 byte-compare+popcount: a
    (32,)-byte bucket row is compared against c and mask-summed.
    """
    p = (i + 1).astype(I32)
    b = p >> 5
    r = p & 31
    base = fm.occ32_counts[b, c.astype(I32)]
    row = fm.occ32_bytes[b]                                  # (..., 32)
    lane = jnp.arange(OPT_ETA, dtype=I32)
    m = (lane < r[..., None]) & (row == c[..., None].astype(jnp.uint8))
    return base + jnp.sum(m, axis=-1).astype(I32)


def occ_base_v(fm: FMArrays, c: jnp.ndarray, i: jnp.ndarray) -> jnp.ndarray:
    """Vectorized Occ with the BASELINE eta=128 2-bit packed layout.

    Requires unpacking 4 codes/byte and a primary-row correction for c==0
    (the sentinel was packed as code 0).  Deliberately more work per query —
    this is the code path whose instruction count the paper's Table 4 blames.
    """
    p = (i + 1).astype(I32)
    b = p >> 7
    r = p & 127
    base = fm.occ128_counts[b, c.astype(I32)]
    packed = fm.occ128_packed[b]                             # (..., 32) uint8
    # unpack to (..., 128) codes, LSB-first within each byte
    shifts = jnp.array([0, 2, 4, 6], dtype=jnp.uint8)
    codes = (packed[..., :, None] >> shifts) & jnp.uint8(3)  # (..., 32, 4)
    codes = codes.reshape(*codes.shape[:-2], BASE_ETA)
    lane = jnp.arange(BASE_ETA, dtype=I32)
    m = (lane < r[..., None]) & (codes == c[..., None].astype(jnp.uint8))
    cnt = base + jnp.sum(m, axis=-1).astype(I32)
    # correction: position `primary` was packed as code 0 but is the sentinel.
    # Only the in-bucket partial count [b*128, p) can overcount it (the bucket
    # base counts come from the true BWT).
    corr = ((c.astype(I32) == 0) & (fm.primary >= (b << 7)) &
            (fm.primary < p)).astype(I32)
    return cnt - corr


def backward_ext_v(fm: FMArrays, k, l, s, c, *, occ_fn=occ_opt_v):
    """Vectorized backward extension. k,l,s: (...,) int32; c: (...,) int32.

    Returns (k', l', s') of string cX.  Invalid bases (c>3) yield s'=0.
    Pass occ_fn=occ_base_v for the original-BWA-MEM occ layout.
    """
    k = k.astype(I32); l = l.astype(I32); s = s.astype(I32)
    cc = jnp.clip(c, 0, 3).astype(I32)
    batch = k.shape
    c4 = jnp.broadcast_to(jnp.arange(4, dtype=I32), batch + (4,))
    i1 = jnp.broadcast_to((k - 1)[..., None], batch + (4,))
    i2 = jnp.broadcast_to((k + s - 1)[..., None], batch + (4,))
    o1 = occ_fn(fm, c4, i1)          # (..., 4)
    o2 = occ_fn(fm, c4, i2)
    ks = fm.C + o1                   # (..., 4)
    ss = o2 - o1                     # (..., 4)
    sent = ((k <= fm.primary) & (fm.primary < k + s)).astype(I32)
    l3 = l + sent
    l2 = l3 + ss[..., 3]
    l1 = l2 + ss[..., 2]
    l0 = l1 + ss[..., 1]
    ls = jnp.stack([l0, l1, l2, l3], axis=-1)
    take = lambda a: jnp.take_along_axis(a, cc[..., None], axis=-1)[..., 0]
    s_out = jnp.where(c > 3, 0, take(ss))
    return take(ks), take(ls), s_out


def forward_ext_v(fm: FMArrays, k, l, s, c, *, occ_fn=occ_opt_v):
    cbar = jnp.where(c > 3, c, 3 - c)
    l2, k2, s2 = backward_ext_v(fm, l, k, s, cbar, occ_fn=occ_fn)
    return k2, l2, s2


# ====================================================================
# numpy twins of the vectorized occ/extension (identical integer math).
# The CPU pipeline uses these to avoid per-dispatch overhead; the jnp
# versions above are the TPU/jit path and the Pallas-kernel oracles.
# ====================================================================

def occ_opt_np(idx: "FMIndex", c: np.ndarray, i: np.ndarray) -> np.ndarray:
    p = (i + 1).astype(np.int64)
    b = p >> 5
    r = (p & 31).astype(np.int32)
    base = idx.occ32_counts[b, c].astype(np.int64)
    rows = idx.occ32_bytes[b]
    lane = np.arange(OPT_ETA, dtype=np.int32)
    m = (lane < r[..., None]) & (rows == c[..., None].astype(np.uint8))
    return base + m.sum(axis=-1)


def occ_base_np(idx: "FMIndex", c: np.ndarray, i: np.ndarray) -> np.ndarray:
    p = (i + 1).astype(np.int64)
    b = p >> 7
    r = (p & 127).astype(np.int32)
    base = idx.occ128_counts[b, c].astype(np.int64)
    packed = idx.occ128_packed[b]
    shifts = np.array([0, 2, 4, 6], dtype=np.uint8)
    codes = (packed[..., :, None] >> shifts) & np.uint8(3)
    codes = codes.reshape(*codes.shape[:-2], BASE_ETA)
    lane = np.arange(BASE_ETA, dtype=np.int32)
    m = (lane < r[..., None]) & (codes == c[..., None].astype(np.uint8))
    cnt = base + m.sum(axis=-1)
    corr = ((c == 0) & (idx.primary >= (b << 7)) &
            (idx.primary < p)).astype(np.int64)
    return cnt - corr


def backward_ext_np(idx: "FMIndex", k, l, s, c, *, occ_np=occ_opt_np):
    k = np.asarray(k, np.int64)
    l = np.asarray(l, np.int64)
    s = np.asarray(s, np.int64)
    c = np.asarray(c, np.int64)
    cc = np.clip(c, 0, 3)
    c4 = np.broadcast_to(np.arange(4), k.shape + (4,))
    i1 = np.broadcast_to((k - 1)[..., None], k.shape + (4,))
    i2 = np.broadcast_to((k + s - 1)[..., None], k.shape + (4,))
    o1 = occ_np(idx, c4, i1)
    o2 = occ_np(idx, c4, i2)
    ks = np.asarray(idx.C) + o1
    ss = o2 - o1
    sent = ((k <= idx.primary) & (idx.primary < k + s)).astype(np.int64)
    l3 = l + sent
    l2 = l3 + ss[..., 3]
    l1 = l2 + ss[..., 2]
    l0 = l1 + ss[..., 1]
    ls = np.stack([l0, l1, l2, l3], axis=-1)
    take = lambda a: np.take_along_axis(a, cc[..., None], axis=-1)[..., 0]
    s_out = np.where(c > 3, 0, take(ss))
    return take(ks), take(ls), s_out


def forward_ext_np(idx: "FMIndex", k, l, s, c, *, occ_np=occ_opt_np):
    c = np.asarray(c, np.int64)
    cbar = np.where(c > 3, c, 3 - c)
    l2, k2, s2 = backward_ext_np(idx, l, k, s, cbar, occ_np=occ_np)
    return k2, l2, s2
