"""Banded Smith-Waterman seed extension (paper §5) — faithful ksw_extend2.

The scalar oracle ``bsw_extend`` is a direct port of bwa-0.7.x
``ksw_extend2`` (including band shrinking, z-drop, first-row/column
initialisation and the exact tie-breaking of max tracking).  It is the
output SPEC: every other implementation must match it bit-for-bit.

``bsw_extend_batch`` is the paper's **inter-task vectorization** (§5.3)
adapted to TPU: W tasks form the vector lane dimension, sequences are SoA
(lane-minor), every DP row is one vectorized step over lanes × columns.
The in-row F recurrence — a first-order max-plus scan the scalar code
resolves serially — is rewritten as a parallel prefix-max over
``t_j + (j+1)·e_ins`` (max-plus algebra), which keeps the whole row data-
parallel on the VPU.  Output is bit-identical to the oracle.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs

I32 = jnp.int32
NEG = -(1 << 28)


@dataclasses.dataclass(frozen=True)
class BSWParams:
    """bwa-mem defaults."""
    a: int = 1            # match score
    b: int = 4            # mismatch penalty
    o_del: int = 6
    e_del: int = 1
    o_ins: int = 6
    e_ins: int = 1
    w: int = 100          # band width
    zdrop: int = 100
    end_bonus: int = 5
    pen_clip5: int = 5
    pen_clip3: int = 5

    def matrix(self) -> np.ndarray:
        """5x5 scoring matrix; row/col 4 is the ambiguous base (-1)."""
        m = np.full((5, 5), -self.b, dtype=np.int32)
        np.fill_diagonal(m, self.a)
        m[4, :] = -1
        m[:, 4] = -1
        return m


@dataclasses.dataclass
class ExtResult:
    score: int
    qle: int
    tle: int
    gtle: int
    gscore: int
    max_off: int


def adjusted_band(qlen: int, p: BSWParams, w: int) -> int:
    """ksw_extend2's w-clamp from max possible indel length."""
    max_ins = int((qlen * p.a + p.end_bonus - p.o_ins) / p.e_ins + 1.0)
    max_ins = max(max_ins, 1)
    w2 = min(w, max_ins)
    max_del = int((qlen * p.a + p.end_bonus - p.o_del) / p.e_del + 1.0)
    max_del = max(max_del, 1)
    return min(w2, max_del)


def bsw_extend(query: np.ndarray, target: np.ndarray, h0: int,
               p: BSWParams, w: int | None = None) -> ExtResult:
    """Scalar oracle — direct ksw_extend2 port. query/target: uint8 codes."""
    qlen, tlen = len(query), len(target)
    assert qlen > 0 and tlen > 0 and h0 > 0
    mat = p.matrix()
    oe_del = p.o_del + p.e_del
    oe_ins = p.o_ins + p.e_ins
    w = adjusted_band(qlen, p, p.w if w is None else w)

    # eh[j] = (h, e); h at loop start = H(i-1, j-1), e = E(i, j)
    eh_h = np.zeros(qlen + 2, dtype=np.int64)
    eh_e = np.zeros(qlen + 2, dtype=np.int64)
    eh_h[0] = h0
    if qlen >= 1:
        eh_h[1] = max(h0 - oe_ins, 0)
    j = 2
    while j <= qlen and eh_h[j - 1] > p.e_ins:
        eh_h[j] = eh_h[j - 1] - p.e_ins
        j += 1

    max_ = h0
    max_i = max_j = -1
    max_ie, gscore = -1, -1
    max_off = 0
    beg, end = 0, qlen
    for i in range(tlen):
        f = 0
        m = 0
        mj = -1
        trow = int(target[i])
        if beg < i - w:
            beg = i - w
        if end > i + w + 1:
            end = i + w + 1
        if end > qlen:
            end = qlen
        if beg == 0:
            h1 = h0 - (p.o_del + p.e_del * (i + 1))
            if h1 < 0:
                h1 = 0
        else:
            h1 = 0
        for jj in range(beg, end):
            # eh[jj] = {H(i-1,jj-1), E(i,jj)}, f = F(i,jj), h1 = H(i,jj-1)
            M = int(eh_h[jj])
            e = int(eh_e[jj])
            eh_h[jj] = h1                      # H(i,jj-1) for next row
            M = M + int(mat[trow, int(query[jj])]) if M else 0
            h = M if M > e else e
            h = h if h > f else f
            h1 = h
            mj = mj if m > h else jj           # last index attaining max
            m = m if m > h else h
            t = M - oe_del
            t = t if t > 0 else 0
            e -= p.e_del
            e = e if e > t else t
            eh_e[jj] = e                       # E(i+1,jj)
            t = M - oe_ins
            t = t if t > 0 else 0
            f -= p.e_ins
            f = f if f > t else t
        eh_h[end] = h1
        eh_e[end] = 0
        if end == qlen:
            max_ie = max_ie if gscore > h1 else i
            gscore = gscore if gscore > h1 else h1
        if m == 0:
            break
        if m > max_:
            max_ = m
            max_i, max_j = i, mj
            off = abs(mj - i)
            max_off = max_off if max_off > off else off
        elif p.zdrop > 0:
            if (i - max_i) > (mj - max_j):
                if max_ - m - ((i - max_i) - (mj - max_j)) * p.e_del > p.zdrop:
                    break
            else:
                if max_ - m - ((mj - max_j) - (i - max_i)) * p.e_ins > p.zdrop:
                    break
        # band update for the next row
        jj = beg
        while jj < end and eh_h[jj] == 0 and eh_e[jj] == 0:
            jj += 1
        beg = jj
        jj = end
        while jj >= beg and eh_h[jj] == 0 and eh_e[jj] == 0:
            jj -= 1
        end = jj + 2 if jj + 2 < qlen else qlen
    return ExtResult(int(max_), max_j + 1, max_i + 1, max_ie + 1,
                     int(gscore), int(max_off))


# =====================================================================
# Inter-task vectorized implementation (paper §5.3, TPU lanes = tasks)
# =====================================================================

def _score_arith(tcode, qcode, a, b):
    """Gather-free scoring identical to BSWParams.matrix(): a on match,
    -b on mismatch, -1 if either code is ambiguous (>= 4)."""
    amb = (tcode >= 4) | (qcode >= 4)
    return jnp.where(amb, -1, jnp.where(tcode == qcode, a, -b)).astype(I32)


def _prefix_max(x, axis_len):
    """Hillis-Steele inclusive prefix max along axis 1 (Pallas-safe)."""
    d = 1
    while d < axis_len:
        shifted = jnp.concatenate(
            [jnp.full(x[:, :d].shape, NEG, x.dtype), x[:, :-d]], axis=1)
        x = jnp.maximum(x, shifted)
        d *= 2
    return x


def bsw_init_state(qlens, h0s, oe_ins, e_ins, qmax: int):
    """First-row fill: eh_h[0]=h0; eh_h[j>=1]=relu(h0-oe_ins-(j-1)e_ins)
    (values that would be <= 0 stay 0, matching the scalar early-exit).

    ``qlens``/``h0s`` are (W, 1) columns, and every per-lane state entry
    stays a (W, 1) column: the TPU kernel cannot reshape a 1-D lane vector
    into the 2-D layout the row arrays broadcast against."""
    W = qlens.shape[0]
    jj = jax.lax.broadcasted_iota(I32, (1, qmax + 1), 1)
    fill = h0s - oe_ins - (jj - 1) * e_ins
    eh_h0 = jnp.where(jj == 0, h0s, jnp.maximum(fill, 0)).astype(I32)
    eh_h0 = jnp.where(jj <= qlens, eh_h0, 0)
    eh_e0 = jnp.zeros((W, qmax + 1), I32)
    col = lambda v: jnp.full((W, 1), v, I32)
    return (eh_h0, eh_e0,
            col(0), qlens.astype(I32),                     # beg, end
            h0s.astype(I32),                               # max
            col(-1), col(-1),                              # max_i, max_j
            col(-1), col(-1),                              # max_ie, gscore
            col(0),                                        # max_off
            col(1))                                        # alive (0/1)


def bsw_row_step(i, st, qs, trow, qlens, tlens, h0s, ws,
                 a, b, o_del, e_del, o_ins, e_ins, zdrop, qmax: int):
    """One DP row for all W lanes — shared by the jnp batch wrapper and the
    Pallas kernel (both must stay bit-identical to the scalar oracle).

    qs (W,qmax) codes; trow (W, 1) is the target base of row i (each
    caller reads it its own way: the TPU kernel has no dynamic lane
    slice); qlens/tlens/h0s/ws and the per-lane state are (W, 1) columns
    (see ``bsw_init_state``)."""
    (eh_h_st, eh_e_st, beg_st, end_st, max_st, max_i_st, max_j_st,
     max_ie_st, gscore_st, max_off_st, alive_st) = st
    W = qs.shape[0]
    oe_del = o_del + e_del
    oe_ins = o_ins + e_ins
    jj = jax.lax.broadcasted_iota(I32, (1, qmax + 1), 1)   # eh index
    jq = jax.lax.broadcasted_iota(I32, (1, qmax), 1)       # query index

    act = (alive_st != 0) & (i < tlens)
    beg = jnp.maximum(beg_st, i - ws)
    end = jnp.minimum(jnp.minimum(end_st, i + ws + 1), qlens)
    h_first = jnp.where(beg == 0,
                        jnp.maximum(h0s - (o_del + e_del * (i + 1)), 0), 0)
    srow = _score_arith(trow, qs, a, b)                     # (W,qmax)
    in_band = (jq >= beg) & (jq < end)
    Hd = eh_h_st[:, :qmax]                                  # H(i-1, j-1)
    Ec = eh_e_st[:, :qmax]                                  # E(i, j)
    Mq = jnp.where(Hd != 0, Hd + srow, 0)
    Mq = jnp.where(in_band, Mq, 0)
    Ec_b = jnp.where(in_band, Ec, 0)
    # F scan (max-plus prefix): F_beg = 0; F_{j+1} = max(F_j - e, t_j)
    t_ins = jnp.maximum(Mq - oe_ins, 0)
    g = jnp.where(in_band, t_ins + (jq + 1) * e_ins, NEG)
    cmax = _prefix_max(g, qmax)
    cmax_excl = jnp.concatenate(
        [jnp.full((W, 1), NEG, I32), cmax[:, :-1]], axis=1)
    F = jnp.maximum(cmax_excl, beg * e_ins) - jq * e_ins
    H = jnp.maximum(jnp.maximum(Mq, Ec_b), F)
    H = jnp.where(in_band, H, 0)
    # row max, LAST index attaining it (scalar tie-break)
    m = jnp.max(H, axis=1, keepdims=True)
    is_max = (H == m) & in_band
    mj = jnp.max(jnp.where(is_max, jq, -1), axis=1, keepdims=True)
    mj = jnp.where(m > 0, mj, -1)
    # h1_final = H(i, end-1) (or first-col value if band empty)
    h_end = jnp.max(jnp.where(jq == end - 1, H, NEG), axis=1, keepdims=True)
    h1_final = jnp.where(end > beg, h_end, h_first)
    # E(i+1, j) and new stored arrays
    t_del = jnp.maximum(Mq - oe_del, 0)
    E_next = jnp.maximum(Ec_b - e_del, t_del)
    # eh_h writes: position j in [beg, end] gets H(i, j-1); beg gets
    # h_first (beg==0) or 0; end gets H(i, end-1).
    Hshift = jnp.concatenate(
        [jnp.zeros((W, 1), I32), H], axis=1)                # H(i, j-1) at j
    wr = (jj >= beg) & (jj <= end) & act
    newh = jnp.where(jj == beg, h_first, Hshift)
    newh = jnp.where(jj == end, h1_final, newh)
    eh_h = jnp.where(wr, newh, eh_h_st)
    Eword = jnp.concatenate([E_next, jnp.zeros((W, 1), I32)], axis=1)
    newe = jnp.where(jj == end, 0, Eword)
    eh_e = jnp.where(wr, newe, eh_e_st)
    # gscore bookkeeping (before the m==0 break, as in scalar code)
    at_end = act & (end == qlens)
    upd_g = at_end & ~(gscore_st > h1_final)
    max_ie = jnp.where(upd_g, i, max_ie_st)
    gscore = jnp.where(upd_g, h1_final, gscore_st)
    # m == 0 -> lane stops (no max/zdrop updates)
    broke0 = act & (m == 0)
    cont = act & ~broke0
    better = cont & (m > max_st)
    off = jnp.abs(mj - i)
    max_off = jnp.where(better, jnp.maximum(max_off_st, off), max_off_st)
    max_ = jnp.where(better, m, max_st)
    max_i = jnp.where(better, i, max_i_st)
    max_j = jnp.where(better, mj, max_j_st)
    # z-drop
    di = i - max_i_st
    dj = mj - max_j_st
    zd = jnp.where(di > dj,
                   max_st - m - (di - dj) * e_del,
                   max_st - m - (dj - di) * e_ins)
    zbreak = cont & ~better & (zdrop > 0) & (zd > zdrop)
    # band update (only lanes continuing past this row)
    nz = (eh_h != 0) | (eh_e != 0)
    cand = nz & (jj >= beg) & (jj < end)
    beg_n = jnp.min(jnp.where(cand, jj, qmax + 1), axis=1, keepdims=True)
    beg_n = jnp.minimum(beg_n, end)
    cand2 = nz & (jj >= beg_n) & (jj <= end)
    jstar = jnp.max(jnp.where(cand2, jj, beg_n - 1), axis=1, keepdims=True)
    end_n = jnp.minimum(jstar + 2, qlens)
    keep = cont & ~zbreak
    return (eh_h, eh_e,
            jnp.where(keep, beg_n, beg_st),
            jnp.where(keep, end_n, end_st),
            jnp.where(cont, max_, max_st),
            jnp.where(cont, max_i, max_i_st),
            jnp.where(cont, max_j, max_j_st),
            max_ie, gscore,
            jnp.where(cont, max_off, max_off_st),
            keep.astype(I32))                       # act implies alive


def bsw_result(st):
    """Final state -> (W, 6) int32 columns: score, qle, tle, gtle, gscore,
    max_off (ExtResult field order)."""
    (_, _, _, _, max_, max_i, max_j, max_ie, gscore, max_off, _) = st
    return jnp.concatenate([max_, max_j + 1, max_i + 1,
                            max_ie + 1, gscore, max_off], axis=1)


@functools.partial(jax.jit, static_argnames=("qmax", "tmax"))
def _bsw_batch_jit(qs, ts, qlens, tlens, h0s, ws, a, b, o_del, e_del,
                   o_ins, e_ins, zdrop, *, qmax: int, tmax: int):
    """W lanes x (tmax rows x qmax cols) masked banded DP.

    qs (W,qmax) int32 codes (pad=4), ts (W,tmax) int32, qlens/tlens/h0s/ws
    (W,) int32.  Returns stacked (score qle tle gtle gscore max_off) (6,W).
    """
    qlens, tlens, h0s, ws = (v[:, None] for v in (qlens, tlens, h0s, ws))
    state = bsw_init_state(qlens, h0s, o_ins + e_ins, e_ins, qmax)

    def row(i, st):
        trow = jax.lax.dynamic_slice_in_dim(ts, i, 1, axis=1)
        return bsw_row_step(i, st, qs, trow, qlens, tlens, h0s, ws,
                            a, b, o_del, e_del, o_ins, e_ins, zdrop, qmax)

    return bsw_result(jax.lax.fori_loop(0, tmax, row, state)).T


def bsw_extend_batch(queries: list[np.ndarray], targets: list[np.ndarray],
                     h0s: list[int], p: BSWParams,
                     ws: list[int] | None = None,
                     qmax: int | None = None, tmax: int | None = None):
    """Inter-task vectorized BSW over a batch of extension tasks.

    Pads to (qmax, tmax), runs all lanes in lockstep, returns a list of
    ExtResult identical to ``bsw_extend`` per task.
    """
    W = len(queries)
    assert W > 0
    qlens = np.array([len(q) for q in queries], np.int32)
    tlens = np.array([len(t) for t in targets], np.int32)
    qmax = qmax or int(qlens.max())
    tmax = tmax or int(tlens.max())
    qs = np.full((W, qmax), 4, np.int32)
    ts = np.full((W, tmax), 4, np.int32)
    for i, (q, t) in enumerate(zip(queries, targets)):
        qs[i, :len(q)] = q
        ts[i, :len(t)] = t
    ws_in = np.array([adjusted_band(int(qlens[i]), p,
                                    p.w if ws is None else int(ws[i]))
                      for i in range(W)], np.int32)
    out = _bsw_batch_jit(
        jnp.asarray(qs), jnp.asarray(ts), jnp.asarray(qlens),
        jnp.asarray(tlens), jnp.asarray(np.array(h0s, np.int32)),
        jnp.asarray(ws_in), p.a, p.b,
        p.o_del, p.e_del, p.o_ins, p.e_ins, p.zdrop,
        qmax=qmax, tmax=tmax)
    out = np.asarray(out)
    return [ExtResult(*(int(v) for v in out[:, i])) for i in range(W)]


def bsw_extend_tasks(queries, targets, h0s, p: BSWParams,
                     ws=None, *, block: int = 256, sort: bool = True,
                     pad: int = 32, batch_fn=None):
    """Batched driver for an ARBITRARY extension-task list (paper §5.3.1).

    The inter-task entry point shared by the pipeline's BSW stage and the
    paired-end mate-rescue fan-out: tasks are length-sorted, cut into
    lockstep blocks of ``block`` lanes, padded to a multiple of ``pad``
    and dispatched through ``bsw_extend_batch``.  Empty-query/target tasks
    short-circuit to the no-op result (ksw_extend is never called with
    empty sequences in bwa).

    ``batch_fn`` substitutes the per-block kernel (same signature as
    ``bsw_extend_batch``, incl. the qmax/tmax padded-shape hints) — the
    "pallas" engine passes ``kernels.bsw.bsw_extend_pallas`` here.

    Returns (results in INPUT order, stats) where stats carries the
    Table-8-style useful/computed cell accounting.
    """
    fn = batch_fn if batch_fn is not None else bsw_extend_batch
    n = len(queries)
    results: list = [None] * n
    stats = dict(tasks=0, cells_useful=0, cells_total=0)
    live = []
    for i in range(n):
        if len(queries[i]) == 0 or len(targets[i]) == 0:
            results[i] = ExtResult(h0s[i], 0, 0, 0, -1, 0)
        else:
            live.append(i)
    if not live:
        return results, stats
    qlens = np.array([len(queries[i]) for i in live])
    tlens = np.array([len(targets[i]) for i in live])
    order = sort_tasks_by_length(qlens, tlens) if sort \
        else np.arange(len(live))
    for s in range(0, len(live), block):
        idxs = [live[j] for j in order[s:s + block]]
        qs = [queries[i] for i in idxs]
        ts = [targets[i] for i in idxs]
        h0b = [h0s[i] for i in idxs]
        wsb = None if ws is None else [ws[i] for i in idxs]
        qmax = -(-max(len(q) for q in qs) // pad) * pad
        tmax = -(-max(len(t) for t in ts) // pad) * pad
        res = fn(qs, ts, h0b, p, ws=wsb, qmax=qmax, tmax=tmax)
        for i, r in zip(idxs, res):
            results[i] = r
        obs.count("bsw_dispatches")
        obs.observe("bsw_block_lanes", len(idxs))
        stats["tasks"] += len(idxs)
        stats["cells_useful"] += int((np.array([len(q) for q in qs]) *
                                      np.array([len(t) for t in ts])).sum())
        stats["cells_total"] += qmax * tmax * len(idxs)
    return results, stats


def sort_tasks_by_length(qlens: np.ndarray, tlens: np.ndarray) -> np.ndarray:
    """Paper §5.3.1: sort tasks by length so same-block lanes are uniform.

    Radix-style two-key sort (target-major) returning the permutation.
    """
    return np.lexsort((np.asarray(qlens), np.asarray(tlens)))


def wasted_cell_stats(qlens, tlens, order, block: int = 128):
    """Table-8-style accounting: useful vs computed DP cells per block."""
    qlens = np.asarray(qlens)[order]
    tlens = np.asarray(tlens)[order]
    total = useful = 0
    for s in range(0, len(qlens), block):
        qb = qlens[s:s + block]
        tb = tlens[s:s + block]
        total += int(qb.max()) * int(tb.max()) * len(qb)
        useful += int((qb * tb).sum())
    return useful, total
