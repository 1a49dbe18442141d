"""SMEM search (paper §2.3/§4.2, Algorithms 2-4; faithful port of bwa smem1).

Two implementations with IDENTICAL output (the paper's hard requirement):

* ``smem1`` / ``seed_strategy1`` / ``collect_smems`` — scalar oracle,
  a direct port of bwa-0.7.x ``bwt_smem1a`` / ``bwt_seed_strategy1`` /
  ``mem_collect_intv`` semantics.

* ``smem1_batch`` / ``seed_strategy1_batch`` / ``collect_smems_batch`` —
  the paper's *batched* reorganization (§3.1 + §4.3): many independent
  (read, start-position) SMEM tasks advance in lockstep rounds; each round
  performs ONE vectorized backward/forward extension for every live task.
  On CPU the paper rejected round-robin batching (extra instructions); on
  TPU it is the only way to keep the VPU busy and is the direct analogue of
  software prefetching — every O_c bucket needed by round r+1 is gathered
  in one vectorized load during round r's step.  See DESIGN.md §2.
  With NumPy occ the rounds run on the host; with a jnp or Pallas occ
  function each call's rounds run on the device as one ``lax.while_loop``
  program, one dispatch and one readback per call.

An SMEM is reported as (k, l, s, qbeg, qend): bi-interval + query span.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
from .fmindex import (FMIndex, backward_ext_np, backward_ext_v,
                      forward_ext_np, forward_ext_v, occ_base_np,
                      occ_opt_np, I32)


@dataclasses.dataclass(frozen=True)
class MemOptions:
    """Seeding options (bwa-mem defaults)."""
    min_seed_len: int = 19
    split_factor: float = 1.5
    split_width: int = 10
    max_mem_intv: int = 20
    max_occ: int = 500        # max SA occurrences sampled per SMEM

    @property
    def split_len(self) -> int:
        return int(self.min_seed_len * self.split_factor + 0.499)


# =====================================================================
# Scalar oracle (port of bwt_smem1a with max_intv=0)
# =====================================================================

def smem1(idx: FMIndex, q: np.ndarray, x: int, min_intv: int = 1):
    """All SMEMs overlapping position x. Returns (smems, ret).

    smems: list of (k, l, s, qbeg, qend); ret: next start position for the
    caller's x-loop (end of the longest forward extension from x).
    """
    L = len(q)
    if q[x] > 3:
        return [], x + 1
    min_intv = max(min_intv, 1)
    ik = idx.init_interval(int(q[x]))
    ik_end = x + 1
    curr: list[tuple[int, int, int, int]] = []   # (k, l, s, end)
    i = x + 1
    broke = False
    while i < L:
        b = int(q[i])
        if b > 3:                       # ambiguous base: stop fwd extension
            curr.append((*ik, ik_end))
            broke = True
            break
        ok = idx.forward_ext(*ik, b)
        if ok[2] != ik[2]:              # interval size changed
            curr.append((*ik, ik_end))
            if ok[2] < min_intv:
                broke = True
                break
        ik = ok
        ik_end = i + 1
        i += 1
    if not broke:
        curr.append((*ik, ik_end))
    curr.reverse()                      # longest forward match first
    ret = curr[0][3]

    prev = curr
    mems: list[tuple[int, int, int, int, int]] = []
    i = x - 1
    while i >= -1:
        c = -1 if (i < 0 or q[i] > 3) else int(q[i])
        curr = []
        for (k, l, s, end) in prev:
            ok = idx.backward_ext(k, l, s, c) if c >= 0 else (0, 0, 0)
            if c < 0 or ok[2] < min_intv:
                if not curr:            # no longer match survived this round
                    if not mems or i + 1 < mems[-1][3]:
                        mems.append((k, l, s, i + 1, end))
            elif not curr or ok[2] != curr[-1][2]:
                curr.append((ok[0], ok[1], ok[2], end))
        if not curr:
            break
        prev = curr
        i -= 1
    mems.reverse()                      # sorted by start coordinate
    return mems, ret


def seed_strategy1(idx: FMIndex, q: np.ndarray, x: int, min_len: int,
                   max_intv: int):
    """Port of bwt_seed_strategy1 (bwa's 3rd seeding round). -> (mem|None, ret)."""
    L = len(q)
    if q[x] > 3:
        return None, x + 1
    ik = idx.init_interval(int(q[x]))
    for i in range(x + 1, L):
        b = int(q[i])
        if b > 3:
            return None, i + 1
        ok = idx.forward_ext(*ik, b)
        if ok[2] < max_intv and i - x >= min_len:
            if ok[2] > 0:
                return (ok[0], ok[1], ok[2], x, i + 1), i + 1
            return None, i + 1
        ik = ok
    return None, L


def collect_smems(idx: FMIndex, q: np.ndarray, opt: MemOptions):
    """Port of mem_collect_intv: 3 seeding passes; sorted (qbeg,qend) order."""
    L = len(q)
    mem: list[tuple[int, int, int, int, int]] = []
    # pass 1: all SMEMs
    x = 0
    while x < L:
        if q[x] < 4:
            ms, x = smem1(idx, q, x, 1)
            mem.extend(m for m in ms if m[4] - m[3] >= opt.min_seed_len)
        else:
            x += 1
    # pass 2: re-seed long, low-occurrence SMEMs
    old = list(mem)
    for (k, l, s, qb, qe) in old:
        if qe - qb < opt.split_len or s > opt.split_width:
            continue
        ms, _ = smem1(idx, q, (qb + qe) >> 1, s + 1)
        mem.extend(m for m in ms if m[4] - m[3] >= opt.min_seed_len)
    # pass 3: LAST-like forward-only seeds
    if opt.max_mem_intv > 0:
        x = 0
        while x < L:
            if q[x] < 4:
                m, x = seed_strategy1(idx, q, x, opt.min_seed_len,
                                      opt.max_mem_intv)
                if m is not None:
                    mem.append(m)
            else:
                x += 1
    mem.sort(key=lambda m: (m[3], m[4]))
    return mem


def frac_rep(mems, l_query: int, max_occ: int) -> float:
    """bwa mem_chain's per-read repeat fraction: the fraction of the read
    covered by SMEMs whose interval size exceeds ``max_occ`` (union of
    query spans, walked in the collectors' sorted (qbeg, qend) order).
    Feeds the q_pe scaling term of the pair-aware MAPQ blend
    (``pe.pairing.blend_mapq``)."""
    b = e = l_rep = 0
    for (k, l, s, qb, qe) in mems:
        if s <= max_occ:
            continue
        if qb > e:
            l_rep += e - b
            b, e = qb, qe
        else:
            e = max(e, qe)
    l_rep += e - b
    return l_rep / l_query if l_query else 0.0


def brute_smems(idx: FMIndex, q: np.ndarray):
    """Brute-force SMEMs by definition (tests only): strictly-increasing
    records of E(s) = longest exact match starting at s."""
    S = idx.seq
    L = len(q)
    E = np.zeros(L, dtype=np.int64)
    text = S.tobytes()
    for s in range(L):
        if q[s] > 3:
            E[s] = s
            continue
        lo, hi = s + 1, L
        # extend greedily: find max e such that q[s:e] occurs in S
        e = s
        while e < L and q[e] <= 3:
            if text.find(q[s:e + 1].tobytes()) < 0:
                break
            e += 1
        E[s] = e
    out = []
    best = -1
    for s in range(L):
        if E[s] > s and E[s] > best:
            out.append((s, int(E[s])))
            best = E[s]
    return out


# =====================================================================
# Batched lockstep implementation (the paper's reorganization)
# =====================================================================

@dataclasses.dataclass
class SmemTaskBatch:
    """Output of a batch of smem1 tasks (padded)."""
    k: np.ndarray      # (T, M) int32
    l: np.ndarray
    s: np.ndarray
    qbeg: np.ndarray
    qend: np.ndarray
    n: np.ndarray      # (T,) number of SMEMs per task
    ret: np.ndarray    # (T,) next x


_NUMPY_OCC = (occ_opt_np, occ_base_np)


def _bucket_tasks(T: int) -> int:
    """Pad the task axis of a device loop to a power of two (floor 32).

    A device loop compiles once per (tasks, read length) shape, and the
    number of tasks differs from call to call (SMEM hops, re-seeding, the
    third round's x-loop); bucketing it bounds the distinct programs to
    O(log T).  Pad tasks have length 0, so they are never valid and take
    no part in any round.
    """
    return max(32, 1 << (T - 1).bit_length())


def _ext_round(idx: FMIndex, which: str, k, l, s, c, occ_np):
    """One vectorized extension round of the host (NumPy) lockstep path,
    the CPU pipeline's fast path: no device dispatch at all."""
    obs.count("smem_rounds")
    fn = forward_ext_np if which == "fwd" else backward_ext_np
    return fn(idx, k, l, s, c, occ_np=occ_np)


def smem1_batch(idx: FMIndex, reads: np.ndarray, lens: np.ndarray,
                task_read: np.ndarray, task_x: np.ndarray,
                task_min_intv: np.ndarray, *,
                occ_fn: Callable = occ_opt_np,
                cap: int | None = None) -> SmemTaskBatch:
    """Lockstep-batched smem1 over T independent tasks.

    Per round, ONE vectorized extension call serves every live (task, entry)
    pair — the TPU analogue of the paper's software-prefetch batching.
    With a NumPy occ function the rounds run on the host; any other occ
    function (jnp, or a Pallas kernel from ``kernels.fmocc.make_occ_fn``)
    runs the whole call, every round of both phases, as one device
    program (``_smem1_loop``).  Output is bit-identical to calling
    ``smem1`` per task either way.
    """
    T = len(task_read)
    L = int(reads.shape[1])
    P = cap or (L + 1)
    if occ_fn not in _NUMPY_OCC:
        return _smem1_device(idx, reads, lens, task_read, task_x,
                             task_min_intv, occ_fn, P)
    q = reads[task_read]                       # (T, L) uint8
    lens_t = lens[task_read].astype(np.int64)
    x = task_x.astype(np.int64)
    min_intv = np.maximum(task_min_intv.astype(np.int64), 1)

    b0 = q[np.arange(T), np.minimum(x, L - 1)].astype(np.int64)
    valid0 = (b0 <= 3) & (x < lens_t)
    C = np.asarray(idx.C)
    cnt4 = np.array([idx.init_interval(c)[2] for c in range(4)], dtype=np.int64)
    b0c = np.clip(b0, 0, 3)
    ik_k = np.where(valid0, C[b0c], 0)
    ik_l = np.where(valid0, C[3 - b0c], 0)
    ik_s = np.where(valid0, cnt4[b0c], 0)
    ik_end = x + 1

    # ---- forward phase ----
    curr_k = np.zeros((T, P), np.int64); curr_l = np.zeros((T, P), np.int64)
    curr_s = np.zeros((T, P), np.int64); curr_e = np.zeros((T, P), np.int64)
    curr_n = np.zeros(T, np.int64)
    alive = valid0.copy()

    def push(mask, kk, ll, ss, ee):
        idxs = np.nonzero(mask)[0]
        slot = curr_n[idxs]
        assert (slot < P).all(), "SMEM forward cap overflow"
        curr_k[idxs, slot] = kk[idxs]; curr_l[idxs, slot] = ll[idxs]
        curr_s[idxs, slot] = ss[idxs]; curr_e[idxs, slot] = ee[idxs]
        curr_n[idxs] += 1

    step = 1
    while alive.any():
        i = x + step
        in_range = alive & (i < lens_t)
        # tasks whose forward run ends exactly at the read end
        ended = alive & ~in_range
        push(ended, ik_k, ik_l, ik_s, ik_end)
        alive = in_range
        if not alive.any():
            break
        b = q[np.arange(T), np.minimum(i, L - 1)].astype(np.int64)
        amb = alive & (b > 3)
        push(amb, ik_k, ik_l, ik_s, ik_end)
        alive = alive & ~amb
        if not alive.any():
            break
        ok_k, ok_l, ok_s = _ext_round(idx, "fwd", ik_k, ik_l, ik_s,
                                      np.clip(b, 0, 4), occ_fn)
        changed = alive & (ok_s != ik_s)
        push(changed, ik_k, ik_l, ik_s, ik_end)
        dead = changed & (ok_s < min_intv)
        alive = alive & ~dead
        upd = alive
        ik_k = np.where(upd, ok_k, ik_k); ik_l = np.where(upd, ok_l, ik_l)
        ik_s = np.where(upd, ok_s, ik_s); ik_end = np.where(upd, i + 1, ik_end)
        step += 1

    # reverse each task's curr list -> longest-first
    for t in np.nonzero(valid0)[0]:
        n = curr_n[t]
        curr_k[t, :n] = curr_k[t, :n][::-1]; curr_l[t, :n] = curr_l[t, :n][::-1]
        curr_s[t, :n] = curr_s[t, :n][::-1]; curr_e[t, :n] = curr_e[t, :n][::-1]
    ret = np.where(valid0, np.where(curr_n > 0, curr_e[:, 0], x + 1), x + 1)

    # ---- backward phase ----
    prev_k, prev_l, prev_s, prev_e = curr_k, curr_l, curr_s, curr_e
    prev_n = curr_n.copy()
    M = P
    mem_k = np.zeros((T, M), np.int64); mem_l = np.zeros((T, M), np.int64)
    mem_s = np.zeros((T, M), np.int64); mem_qb = np.zeros((T, M), np.int64)
    mem_qe = np.zeros((T, M), np.int64); mem_n = np.zeros(T, np.int64)
    active = valid0 & (prev_n > 0)
    i_t = x - 1                               # per-task backward position

    while active.any():
        c = np.full(T, -1, np.int64)
        pos_ok = active & (i_t >= 0)
        bi = q[np.arange(T), np.maximum(np.minimum(i_t, L - 1), 0)].astype(np.int64)
        c = np.where(pos_ok & (bi <= 3), bi, -1)
        # one vectorized backward extension for ALL live entries
        cc = np.where(c >= 0, c, 4)[:, None].repeat(P, 1)
        ok_k, ok_l, ok_s = _ext_round(idx, "bwd", prev_k, prev_l, prev_s,
                                      cc, occ_fn)
        # per-slot sweep, vectorized ACROSS tasks (the entry-list order
        # semantics only reference per-task running state: the count of
        # kept entries and the last kept size)
        pmax = int(prev_n[active].max()) if active.any() else 0
        n_new = np.zeros(T, np.int64)
        last_s = np.full(T, -1, np.int64)
        for j in range(pmax):
            live = active & (j < prev_n)
            fails = live & ((c < 0) | (ok_s[:, j] < min_intv))
            # emission: first failing entry this round, not contained
            emit = fails & (n_new == 0) & (
                (mem_n == 0) |
                (i_t + 1 < mem_qb[np.arange(T), np.maximum(mem_n - 1, 0)]))
            eidx = np.nonzero(emit)[0]
            if eidx.size:
                m = mem_n[eidx]
                assert (m < M).all(), "SMEM mem cap overflow"
                mem_k[eidx, m] = prev_k[eidx, j]
                mem_l[eidx, m] = prev_l[eidx, j]
                mem_s[eidx, m] = prev_s[eidx, j]
                mem_qb[eidx, m] = i_t[eidx] + 1
                mem_qe[eidx, m] = prev_e[eidx, j]
                mem_n[eidx] += 1
            keep = live & ~fails & ((n_new == 0) | (ok_s[:, j] != last_s))
            kidx = np.nonzero(keep)[0]
            if kidx.size:
                slot = n_new[kidx]
                curr_k[kidx, slot] = ok_k[kidx, j]
                curr_l[kidx, slot] = ok_l[kidx, j]
                curr_s[kidx, slot] = ok_s[kidx, j]
                curr_e[kidx, slot] = prev_e[kidx, j]
                n_new[kidx] += 1
                last_s[kidx] = ok_s[kidx, j]
        prev_n = np.where(active, n_new, prev_n)
        active = active & (n_new > 0)
        prev_k, curr_k = curr_k, prev_k
        prev_l, curr_l = curr_l, prev_l
        prev_s, curr_s = curr_s, prev_s
        prev_e, curr_e = curr_e, prev_e
        active = active & (i_t >= 0)
        i_t = i_t - 1

    # reverse mems -> sorted by start coordinate
    for t in range(T):
        n = mem_n[t]
        if n:
            mem_k[t, :n] = mem_k[t, :n][::-1]; mem_l[t, :n] = mem_l[t, :n][::-1]
            mem_s[t, :n] = mem_s[t, :n][::-1]
            mem_qb[t, :n] = mem_qb[t, :n][::-1]; mem_qe[t, :n] = mem_qe[t, :n][::-1]
    return SmemTaskBatch(mem_k, mem_l, mem_s, mem_qb, mem_qe, mem_n, ret)


def seed_strategy1_batch(idx: FMIndex, reads: np.ndarray, lens: np.ndarray,
                         task_read: np.ndarray, task_x: np.ndarray,
                         min_len: int, max_intv: int, *,
                         occ_fn: Callable = occ_opt_np):
    """Lockstep-batched bwt_seed_strategy1.

    Returns ``(out, has, ret)``: per task the seed (k, l, s, qbeg, qend),
    whether there is one, and the next x.  Like ``smem1_batch``, a
    non-NumPy occ function runs the call as one device program
    (``_seed1_loop``)."""
    if occ_fn not in _NUMPY_OCC:
        return _seed1_device(idx, reads, lens, task_read, task_x, min_len,
                             max_intv, occ_fn)
    T = len(task_read)
    L = int(reads.shape[1])
    q = reads[task_read]
    lens_t = lens[task_read].astype(np.int64)
    x = task_x.astype(np.int64)

    b0 = q[np.arange(T), np.minimum(x, L - 1)].astype(np.int64)
    valid0 = (b0 <= 3) & (x < lens_t)
    C = np.asarray(idx.C)
    cnt4 = np.array([idx.init_interval(c)[2] for c in range(4)], dtype=np.int64)
    b0c = np.clip(b0, 0, 3)
    ik_k = np.where(valid0, C[b0c], 0)
    ik_l = np.where(valid0, C[3 - b0c], 0)
    ik_s = np.where(valid0, cnt4[b0c], 0)

    out = np.zeros((T, 5), np.int64)   # k,l,s,qb,qe
    has = np.zeros(T, bool)
    ret = np.where(valid0, lens_t, x + 1)
    alive = valid0.copy()
    step = 1
    while alive.any():
        i = x + step
        in_range = alive & (i < lens_t)
        alive = in_range
        if not alive.any():
            break
        b = q[np.arange(T), np.minimum(i, L - 1)].astype(np.int64)
        amb = alive & (b > 3)
        ret = np.where(amb, i + 1, ret)
        alive = alive & ~amb
        if not alive.any():
            break
        ok_k, ok_l, ok_s = _ext_round(idx, "fwd", ik_k, ik_l, ik_s,
                                      np.clip(b, 0, 4), occ_fn)
        hit = alive & (ok_s < max_intv) & ((i - x) >= min_len)
        good = hit & (ok_s > 0)
        out[good, 0] = ok_k[good]; out[good, 1] = ok_l[good]
        out[good, 2] = ok_s[good]; out[good, 3] = x[good]
        out[good, 4] = i[good] + 1
        has |= good
        ret = np.where(hit, i + 1, ret)
        alive = alive & ~hit
        upd = alive
        ik_k = np.where(upd, ok_k, ik_k); ik_l = np.where(upd, ok_l, ik_l)
        ik_s = np.where(upd, ok_s, ik_s)
        step += 1
    return out, has, ret


# =====================================================================
# Device loops: one program per smem1_batch / seed_strategy1_batch call
# =====================================================================
#
# The rounds of the NumPy path above, as ``lax.while_loop``s over
# fixed-shape (tasks, slots) state, so a call costs one dispatch and one
# readback instead of one per round.  The occ lookups stay inside the loop
# body (the Pallas kernel where the occ function is one).
#
# Entry lists keep their slots instead of being compacted: the forward
# phase pushes from the last slot down, so slots P-n..P-1 hold the list
# longest-first (the NumPy path's reversed list); the backward phase marks
# the entries that survive a round in ``valid`` and leaves them where they
# are, since compaction keeps their order anyway.  Emitted SMEMs also fill
# the last slots first and are moved to the front, in start order, at the
# end.

def _base_at(q, pos):
    """q[t, pos[t]] for every task, pos clipped into the read."""
    pos = jnp.clip(pos, 0, q.shape[1] - 1)
    return jnp.take_along_axis(q, pos[:, None], axis=1)[:, 0]


def _take(a, col):
    """a[t, col[t]] for every task."""
    return jnp.take_along_axis(a, col[:, None], axis=1)[:, 0]


def _put(a, mask, col, v):
    """a[t, col[t]] = v[t] where mask[t]."""
    slots = jnp.arange(a.shape[1], dtype=I32)[None, :]
    return jnp.where(mask[:, None] & (slots == col[:, None]), v[:, None], a)


def _first_interval(fm, q, x, lens):
    """Bi-interval of each task's first base (``FMIndex.init_interval``),
    and whether the task starts on a base at all."""
    b0 = _base_at(q, x)
    valid = (b0 <= 3) & (x < lens)
    b = jnp.clip(b0, 0, 3)
    size = jnp.concatenate([fm.C[1:] - fm.C[:3], (fm.N - fm.C[3])[None]])
    zero = jnp.zeros_like(b)
    return (valid, jnp.where(valid, fm.C[b], zero),
            jnp.where(valid, fm.C[3 - b], zero),
            jnp.where(valid, size[b], zero))


def _smem1_loop(fm, q, lens, x, min_intv, *, occ_fn, P):
    """``smem1_batch``'s rounds on the device.  q (Tp, L) uint8; lens, x,
    min_intv (Tp,) int32.  Returns the (Tp, P) mem arrays k, l, s, qbeg,
    qend in start order, their counts, ret, and the rounds run."""
    q = q.astype(I32)
    Tp = q.shape[0]
    slots = jnp.arange(P, dtype=I32)[None, :]
    valid0, k, l, s = _first_interval(fm, q, x, lens)
    grid = jnp.zeros((Tp, P), I32)
    none = jnp.zeros(Tp, I32)

    # ---- forward phase: every task pushes at most one entry a step ----
    def fwd(st):
        step, alive, k, l, s, end, ck, cl, cs, ce, cn, rounds = st
        i = x + step
        in_range = alive & (i < lens)
        ended = alive & ~in_range
        b = _base_at(q, i)
        amb = in_range & (b > 3)
        alive = in_range & ~amb
        rounds = rounds + jnp.any(alive).astype(I32)
        ok_k, ok_l, ok_s = forward_ext_v(fm, k, l, s, jnp.clip(b, 0, 4),
                                         occ_fn=occ_fn)
        changed = alive & (ok_s != s)
        push = ended | amb | changed
        at = P - 1 - cn
        ck = _put(ck, push, at, k); cl = _put(cl, push, at, l)
        cs = _put(cs, push, at, s); ce = _put(ce, push, at, end)
        cn = cn + push.astype(I32)
        alive = alive & ~(changed & (ok_s < min_intv))
        k = jnp.where(alive, ok_k, k); l = jnp.where(alive, ok_l, l)
        s = jnp.where(alive, ok_s, s); end = jnp.where(alive, i + 1, end)
        return step + 1, alive, k, l, s, end, ck, cl, cs, ce, cn, rounds

    st = lax.while_loop(lambda st: jnp.any(st[1]), fwd,
                        (jnp.int32(1), valid0, k, l, s, x + 1,
                         grid, grid, grid, grid, none, jnp.int32(0)))
    pk, pl, ps, pe, cn, rounds = st[6:]
    longest = jnp.clip(P - cn, 0, P - 1)
    ret = jnp.where(valid0 & (cn > 0), _take(pe, longest), x + 1)

    # ---- backward phase: each task emits at most one SMEM a round ----
    def bwd(st):
        i, active, valid, pk, pl, ps, mk, ml, ms, mb, me, mn, rounds = st
        bi = _base_at(q, i)
        c = jnp.where(active & (i >= 0) & (bi <= 3), bi, -1)
        cc = jnp.broadcast_to(jnp.where(c >= 0, c, 4)[:, None], (Tp, P))
        ok_k, ok_l, ok_s = backward_ext_v(fm, pk, pl, ps, cc, occ_fn=occ_fn)
        live = active[:, None] & valid
        fails = live & ((c < 0)[:, None] | (ok_s < min_intv[:, None]))
        good = live & ~fails
        # a surviving entry is kept when its size differs from the last
        # kept size, which is the size of the surviving entry before it
        before = lax.cummax(jnp.where(good, slots, -1), axis=1)
        before = jnp.pad(before[:, :-1], ((0, 0), (1, 0)),
                         constant_values=-1)
        keep = good & ((before < 0) | (
            ok_s != jnp.take_along_axis(ok_s, jnp.maximum(before, 0),
                                        axis=1)))
        # the first failing entry is emitted when no entry before it
        # survives and it is not contained in the last SMEM emitted
        j = jnp.min(jnp.where(fails, slots, P), axis=1)
        first_good = jnp.min(jnp.where(good, slots, P), axis=1)
        last_qb = _take(mb, jnp.clip(P - mn, 0, P - 1))
        emit = (j < first_good) & ((mn == 0) | (i + 1 < last_qb))
        j = jnp.minimum(j, P - 1)
        at = P - 1 - mn
        mk = _put(mk, emit, at, _take(pk, j)); ml = _put(ml, emit, at, _take(pl, j))
        ms = _put(ms, emit, at, _take(ps, j)); mb = _put(mb, emit, at, i + 1)
        me = _put(me, emit, at, _take(pe, j))
        mn = mn + emit.astype(I32)
        zero = jnp.zeros_like(ok_k)
        pk = jnp.where(keep, ok_k, zero); pl = jnp.where(keep, ok_l, zero)
        ps = jnp.where(keep, ok_s, zero)
        active = active & jnp.any(keep, axis=1) & (i >= 0)
        return (i - 1, active, keep, pk, pl, ps, mk, ml, ms, mb, me, mn,
                rounds + 1)

    valid = valid0[:, None] & (slots >= (P - cn)[:, None])
    st = lax.while_loop(lambda st: jnp.any(st[1]), bwd,
                        (x - 1, valid0 & (cn > 0), valid, pk, pl, ps,
                         grid, grid, grid, grid, grid, none, rounds))
    mems, mn, rounds = st[6:11], st[11], st[12]
    src = jnp.clip(P - mn[:, None] + slots, 0, P - 1)
    head = slots < mn[:, None]
    mems = [jnp.where(head, jnp.take_along_axis(m, src, axis=1), 0)
            for m in mems]
    return (*mems, mn, ret, rounds)


def _seed1_loop(fm, q, lens, x, min_len, max_intv, *, occ_fn):
    """``seed_strategy1_batch``'s rounds on the device.  Returns the
    (Tp, 5) seeds (k, l, s, qbeg, qend), whether each task has one, ret,
    and the rounds run."""
    q = q.astype(I32)
    valid0, k, l, s = _first_interval(fm, q, x, lens)

    def body(st):
        step, alive, k, l, s, out, has, ret, rounds = st
        i = x + step
        alive = alive & (i < lens)
        b = _base_at(q, i)
        amb = alive & (b > 3)
        ret = jnp.where(amb, i + 1, ret)
        alive = alive & ~amb
        rounds = rounds + jnp.any(alive).astype(I32)
        ok_k, ok_l, ok_s = forward_ext_v(fm, k, l, s, jnp.clip(b, 0, 4),
                                         occ_fn=occ_fn)
        hit = alive & (ok_s < max_intv) & (i - x >= min_len)
        good = hit & (ok_s > 0)
        seed = jnp.stack([ok_k, ok_l, ok_s, x, i + 1], axis=1)
        out = jnp.where(good[:, None], seed, out)
        ret = jnp.where(hit, i + 1, ret)
        alive = alive & ~hit
        k = jnp.where(alive, ok_k, k); l = jnp.where(alive, ok_l, l)
        s = jnp.where(alive, ok_s, s)
        return step + 1, alive, k, l, s, out, has | good, ret, rounds

    st = lax.while_loop(lambda st: jnp.any(st[1]), body,
                        (jnp.int32(1), valid0, k, l, s,
                         jnp.zeros((q.shape[0], 5), I32),
                         jnp.zeros_like(valid0),
                         jnp.where(valid0, lens, x + 1), jnp.int32(0)))
    return st[5:]


_smem1_loop_j = jax.jit(_smem1_loop, static_argnames=("occ_fn", "P"))
_seed1_loop_j = jax.jit(_seed1_loop, static_argnames=("occ_fn",))


def _device_tasks(reads, lens, task_read, task_x):
    """The tasks' reads, lengths and start positions, padded to
    ``_bucket_tasks`` (pad tasks have length 0)."""
    T = len(task_read)
    Tp = _bucket_tasks(T)
    q = np.zeros((Tp, reads.shape[1]), np.uint8)
    q[:T] = reads[task_read]
    lens_t = np.zeros(Tp, np.int32)
    lens_t[:T] = lens[task_read]
    x = np.zeros(Tp, np.int32)
    x[:T] = task_x
    return q, lens_t, x


def _run_loop(loop, T, occ_fn, *args, **static):
    """Dispatch one device loop and read its results back once.

    With a Pallas occ function the dispatch and its wait are one
    ``kernel.fmocc`` span, whose ``tasks`` is T summed over the rounds
    the loop ran: what the per-round spans of a host loop would add up
    to.  Returns the results cut to the T tasks, as int64."""
    obs.count("smem_occ_dispatches")
    pallas = getattr(occ_fn, "is_pallas", False)
    if pallas:
        obs.count("kernel_fmocc_dispatches")
    span = obs.span("kernel.fmocc", cat="kernel") if pallas else obs.NULL_SPAN
    with span as sp:
        *out, rounds = jax.device_get(loop(*args, occ_fn=occ_fn, **static))
        rounds = int(rounds)
        sp.set_args(tasks=T * rounds)
    obs.count("smem_rounds", rounds)
    return [np.asarray(v[:T], np.int64) for v in out]


def _smem1_device(idx, reads, lens, task_read, task_x, task_min_intv,
                  occ_fn, P) -> SmemTaskBatch:
    T = len(task_read)
    q, lens_t, x = _device_tasks(reads, lens, task_read, task_x)
    min_intv = np.ones(len(x), np.int32)
    min_intv[:T] = np.maximum(task_min_intv, 1)
    return SmemTaskBatch(*_run_loop(_smem1_loop_j, T, occ_fn, idx.device(),
                                    q, lens_t, x, min_intv, P=P))


def _seed1_device(idx, reads, lens, task_read, task_x, min_len, max_intv,
                  occ_fn):
    q, lens_t, x = _device_tasks(reads, lens, task_read, task_x)
    out, has, ret = _run_loop(_seed1_loop_j, len(task_read), occ_fn,
                              idx.device(), q, lens_t, x, np.int32(min_len),
                              np.int32(max_intv))
    return out, has.astype(bool), ret


def collect_smems_batch(idx: FMIndex, reads: np.ndarray, lens: np.ndarray,
                        opt: MemOptions, *, occ_fn: Callable = occ_opt_np):
    """Batched mem_collect_intv over a whole read batch (the Fig-2 workflow).

    Returns per-read python lists of (k,l,s,qb,qe), identical to
    ``collect_smems`` per read.
    """
    R, L = reads.shape
    lens = np.asarray(lens, np.int64)
    mems: list[list[tuple[int, int, int, int, int]]] = [[] for _ in range(R)]

    # ---- pass 1: x-loop in lockstep rounds over reads ----
    x = np.zeros(R, np.int64)
    # skip leading ambiguous bases without an smem1 call (bwa's else ++x)
    while True:
        active = x < lens
        if not active.any():
            break
        cur_b = reads[np.arange(R), np.minimum(x, L - 1)]
        amb = active & (cur_b > 3)
        x[amb] += 1
        run = active & ~amb
        if not run.any():
            continue
        tr = np.nonzero(run)[0]
        batch = smem1_batch(idx, reads, lens, tr, x[tr],
                            np.ones(len(tr), np.int64), occ_fn=occ_fn)
        for ti, r in enumerate(tr):
            for m in range(batch.n[ti]):
                if batch.qend[ti, m] - batch.qbeg[ti, m] >= opt.min_seed_len:
                    mems[r].append((int(batch.k[ti, m]), int(batch.l[ti, m]),
                                    int(batch.s[ti, m]), int(batch.qbeg[ti, m]),
                                    int(batch.qend[ti, m])))
        x[tr] = batch.ret

    # ---- pass 2: re-seeding, all tasks known upfront -> one batch ----
    t_read, t_x, t_mi = [], [], []
    for r in range(R):
        for (k, l, s, qb, qe) in list(mems[r]):
            if qe - qb < opt.split_len or s > opt.split_width:
                continue
            t_read.append(r); t_x.append((qb + qe) >> 1); t_mi.append(s + 1)
    if t_read:
        batch = smem1_batch(idx, reads, lens, np.array(t_read),
                            np.array(t_x), np.array(t_mi), occ_fn=occ_fn)
        for ti, r in enumerate(t_read):
            for m in range(batch.n[ti]):
                if batch.qend[ti, m] - batch.qbeg[ti, m] >= opt.min_seed_len:
                    mems[r].append((int(batch.k[ti, m]), int(batch.l[ti, m]),
                                    int(batch.s[ti, m]), int(batch.qbeg[ti, m]),
                                    int(batch.qend[ti, m])))

    # ---- pass 3: forward-only seeds, lockstep x-loop ----
    if opt.max_mem_intv > 0:
        x = np.zeros(R, np.int64)
        while True:
            active = x < lens
            if not active.any():
                break
            cur_b = reads[np.arange(R), np.minimum(x, L - 1)]
            amb = active & (cur_b > 3)
            x[amb] += 1
            run = active & ~amb
            if not run.any():
                continue
            tr = np.nonzero(run)[0]
            out, has, ret = seed_strategy1_batch(
                idx, reads, lens, tr, x[tr], opt.min_seed_len,
                opt.max_mem_intv, occ_fn=occ_fn)
            for ti, r in enumerate(tr):
                if has[ti]:
                    mems[r].append(tuple(int(v) for v in out[ti]))
            x[tr] = ret

    for r in range(R):
        mems[r].sort(key=lambda m: (m[3], m[4]))
    return mems
