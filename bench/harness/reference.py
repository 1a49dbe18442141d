"""The plain reference that decides ``correct``: SAM records against the
simulated truth, with nothing taken from the aligner.

The benchmark made the reference sequence and every read from the seed,
so it knows where each read came from.  For every read that is due it
takes the read's one primary SAM record and computes, in plain numpy:

* ``missing``: due reads with no primary record, or with more than one;
* ``nm_mismatch``: mapped primary records whose ``NM`` tag differs from
  the edit distance of their own POS and CIGAR against the reference, or
  whose CIGAR does not cover the read or runs off the reference;
* ``score_gap_max``: the widest gap by which the score of the reported
  alignment (its CIGAR scored on the reference with the configuration's
  scoring) lies below the best local alignment of the read at its true
  locus, found by a full affine-gap Smith-Waterman over a window around
  that locus.  An unmapped read scores 0;
* ``locus_bad``: mapped primary records of reads whose true span lies
  outside every planted repeat (with ``LOCUS_MARGIN`` bases to spare)
  and whose reported span does not overlap it: such a read has one
  place in the reference, and an equal score elsewhere is no excuse;
* ``nm_mismatch`` also covers every mapped secondary and supplementary
  record, each against its own POS and CIGAR.

Paired cells add three numbers about pairing and the mate fields, which
PE pairing and finalize write:

* ``mate_bad``: primary records whose FLAG bits 0x1/0x8/0x10/0x20,
  RNEXT, PNEXT or TLEN differ from what bwa mem (``mem_aln2sam``)
  writes given the two ends' own RNAME, POS, strand and CIGAR, or whose
  0x40/0x80 bits do not name one first and one second end; an unmapped
  end with a mapped mate takes the mate's RNAME, POS and strand;
* ``pair_locus_bad``: pairs that bwa mem's pairing and mate rescue put
  at their true loci and the run did not (see ``_pair_loci``): an end
  on a repeat copy is placed by its mate;
* ``proper_bad``: pairs whose 0x2 bit disagrees with the insert-size
  distribution the traffic drew from: two ends mapped in FR orientation
  on one contig with |TLEN| within ``PROPER_IN_SD`` standard deviations
  of the mean are a proper pair (bwa's bounds are at least that wide),
  and a pair that is not FR on one contig, or has an end unmapped, or
  lies beyond ``PROPER_OUT_SD`` standard deviations, is not.

bwa-mem's alignment is heuristic and prefers an end-to-end alignment to a
clipped one within the clipping penalties, so sound runs read a small
gap; a read that the aligner misplaces, clips wrongly or fails to map
reads a gap of tens of points.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

from .simulate import revcomp_read

_CIGAR = re.compile(r"(\d+)([MIDNSHP=X])")
PAD = 5            # window code beyond the reference's ends
WINDOW_PAD = 24    # reference bases on each side of the true locus
LOCUS_MARGIN = 50  # repeat-free bases a read's true span needs each side
PROPER_IN_SD = 4.0
PROPER_OUT_SD = 8.0
PAIR_SCORE_SLACK = 5
MIN_RESCUE_SCORE = 30   # bwa -T: an alignment scoring less is not output


@dataclasses.dataclass(frozen=True)
class Scoring:
    match: int = 1
    mismatch: int = 4
    gap_open: int = 6
    gap_extend: int = 1
    ambiguous: int = -1

    @classmethod
    def from_config(cls, d: dict) -> "Scoring":
        return cls(**d)


@dataclasses.dataclass
class Due:
    """One read that the window has to answer: its name in the SAM
    (``qname``), which end (0 single-end, 1 or 2 paired), its bases as
    sequenced, and its true forward-strand start and strand."""
    qname: str
    end: int
    read: np.ndarray
    pos: int
    rev: bool


def _subst(q: np.ndarray, t: np.ndarray, sc: Scoring) -> np.ndarray:
    s = np.where(q == t, sc.match, -sc.mismatch)
    s = np.where((q > 3) | (t > 3), sc.ambiguous, s)
    return np.where(t == PAD, -10_000, s).astype(np.int32)


def local_best(queries: np.ndarray, windows: np.ndarray,
               sc: Scoring) -> np.ndarray:
    """Best local alignment score of each query against its window.

    ``queries`` (n, L) and ``windows`` (n, W) hold base codes (0-3, N=4,
    PAD beyond the reference).  Full Smith-Waterman with affine gaps
    (a gap of length k costs open + k * extend), one row of the matrix per
    query base, vectorised over the queries and the window.  The
    horizontal gap state is a running maximum over the row: opening it
    from a cell whose best came from the same state never beats
    extending that state, so the scan over the row without it is exact.
    """
    n, L = queries.shape
    W = windows.shape[1]
    oe = sc.gap_open + sc.gap_extend
    neg = np.int32(-(1 << 28))
    H = np.zeros((n, W + 1), np.int32)         # row i-1; column 0 is 0
    V = np.full((n, W + 1), neg, np.int32)     # gap in the reference
    ke = (np.arange(W + 1, dtype=np.int32) * sc.gap_extend)[None, :]
    best = np.zeros(n, np.int32)
    for i in range(L):
        s = _subst(queries[:, i:i + 1], windows, sc)
        V = np.maximum(H - oe, V - sc.gap_extend)
        h0 = np.maximum(np.maximum(H[:, :-1] + s, V[:, 1:]), 0)
        a = np.concatenate([np.zeros((n, 1), np.int32), h0], axis=1) + ke
        m = np.maximum.accumulate(a, axis=1)
        hrow = np.maximum(h0, m[:, :-1] - sc.gap_open - ke[:, 1:])
        H = np.concatenate([np.zeros((n, 1), np.int32), hrow], axis=1)
        best = np.maximum(best, hrow.max(axis=1))
    return best


def truth_windows(due: list[Due], ref: np.ndarray):
    """(queries in forward-strand orientation, reference windows)."""
    L = max(len(d.read) for d in due)
    W = L + 2 * WINDOW_PAD
    q = np.full((len(due), L), 4, np.uint8)
    t = np.full((len(due), W), PAD, np.uint8)
    for r, d in enumerate(due):
        fwd = revcomp_read(d.read) if d.rev else d.read
        q[r, :len(fwd)] = fwd
        lo = d.pos - WINDOW_PAD
        a, b = max(lo, 0), min(lo + W, len(ref))
        t[r, a - lo:b - lo] = ref[a:b]
    return q, t


def score_record(read: np.ndarray, ref: np.ndarray, pos: int, cigar: str,
                 sc: Scoring):
    """(score, edit distance) of a record's alignment, recomputed from its
    POS (0-based) and CIGAR; None when the CIGAR does not cover the read
    or leaves the reference.  ``read`` is in SAM orientation."""
    ops = [(int(n), op) for n, op in _CIGAR.findall(cigar)]
    if not ops or "".join(f"{n}{op}" for n, op in ops) != cigar:
        return None
    qi, ri = 0, pos
    score = nm = 0
    for n, op in ops:
        if op in "SH":
            qi += n
        elif op == "M":
            if qi + n > len(read) or ri < 0 or ri + n > len(ref):
                return None
            q, t = read[qi:qi + n], ref[ri:ri + n]
            score += int(_subst(q, t, sc).sum())
            nm += int((q != t).sum())
            qi += n
            ri += n
        elif op == "I":
            score -= sc.gap_open + sc.gap_extend * n
            nm += n
            qi += n
        elif op == "D":
            score -= sc.gap_open + sc.gap_extend * n
            nm += n
            ri += n
        else:
            return None
    if qi != len(read) or ri > len(ref):
        return None
    return score, nm


def records(sam_lines) -> tuple[dict, dict]:
    """({(qname, end): [fields, ...]} of the primary records, the same of
    the secondary and supplementary ones)."""
    prim: dict = {}
    other: dict = {}
    for ln in sam_lines:
        if not ln or ln.startswith("@"):
            continue
        f = ln.rstrip("\n").split("\t")
        flag = int(f[1])
        end = 1 if flag & 0x40 else 2 if flag & 0x80 else 0
        (other if flag & 0x900 else prim).setdefault((f[0], end),
                                                      []).append(f)
    return prim, other


def primaries(sam_lines) -> dict:
    """{(qname, end): [fields, ...]} of the primary records."""
    return records(sam_lines)[0]


def ref_len(cigar: str) -> int:
    """Reference bases a CIGAR consumes (M, D, N, = and X)."""
    return sum(int(n) for n, op in _CIGAR.findall(cigar) if op in "MDN=X")


def repeat_free(repeats, pos: int, length: int) -> bool:
    """Whether [pos - LOCUS_MARGIN, pos + length + LOCUS_MARGIN) misses
    every interval of ``repeats`` ((starts, ends), sorted, disjoint)."""
    starts, ends = repeats
    a, b = pos - LOCUS_MARGIN, pos + length + LOCUS_MARGIN
    i = int(np.searchsorted(starts, b, side="left")) - 1
    return i < 0 or ends[i] <= a


def mate_fields(f: list, m: list) -> tuple:
    """(FLAG bits 0x1|0x8|0x10|0x20, RNAME, POS, RNEXT, PNEXT, TLEN) that
    bwa mem writes for the end ``f`` of a pair whose other end is ``m``
    (both SAM records split into fields), from each end's own mapping."""
    ff, mf = int(f[1]), int(m[1])
    f_map, m_map = not ff & 0x4, not mf & 0x4
    f_rev, m_rev = bool(ff & 0x10), bool(mf & 0x10)
    rname, pos = f[2], int(f[3])
    if not f_map and m_map:          # the unmapped end takes its mate's place
        rname, pos, f_rev = m[2], int(m[3]), m_rev
    elif f_map and not m_map:        # and the mapped end lends its own
        m_rev = f_rev
    elif not f_map:                  # neither end has a place
        rname, pos, f_rev, m_rev = "*", 0, False, False
    bits = 0x1 | (0 if m_map else 0x8) | (0x10 if f_rev else 0)
    bits |= 0x20 if m_rev else 0
    if m_map:
        rnext = "=" if m[2] == rname else m[2]
        pnext = int(m[3])
        tlen = 0
        if f_map and m[2] == f[2]:
            p0 = int(f[3]) - 1 + (ref_len(f[5]) - 1 if ff & 0x10 else 0)
            p1 = int(m[3]) - 1 + (ref_len(m[5]) - 1 if mf & 0x10 else 0)
            tlen = -(p0 - p1 + (1 if p0 > p1 else -1 if p0 < p1 else 0))
    elif f_map:
        rnext, pnext, tlen = "=", pos, 0
    else:
        rnext, pnext, tlen = "*", 0, 0
    return bits, rname, pos, rnext, pnext, tlen


def _as_written(f: list) -> tuple:
    return (int(f[1]) & 0x39, f[2], int(f[3]), f[6], int(f[7]), int(f[8]))


def proper_expected(f1: list, f2: list, insert) -> bool | None:
    """True where the pair (the primary records of its two ends) has to
    carry 0x2, False where it may not, None where either is bwa's."""
    mean, sd = insert
    a, b = int(f1[1]), int(f2[1])
    if a & 0x4 or b & 0x4 or f1[2] != f2[2] or not (a ^ b) & 0x10:
        return False
    fwd, rev = (f1, f2) if not a & 0x10 else (f2, f1)
    tlen = mate_fields(fwd, rev)[5]
    if tlen <= 0:                    # RF: the reverse end starts first
        return False
    if abs(tlen - mean) <= PROPER_IN_SD * sd:
        return True
    if abs(tlen - mean) > PROPER_OUT_SD * sd:
        return False
    return None


def _check_record(f: list, d: Due, ref: np.ndarray, sc: Scoring,
                  rname: str):
    """(score, edit distance) of a mapped record of the read ``d`` when
    its NM tag is that of its own POS and CIGAR, else None."""
    flag = int(f[1])
    tags = dict(t.split(":", 1) for t in f[11:])
    read = revcomp_read(d.read) if flag & 0x10 else d.read
    sr = (score_record(read, ref, int(f[3]) - 1, f[5], sc)
          if f[2] == rname else None)
    if sr is None or tags.get("NM") != f"i:{sr[1]}":
        return None
    return sr


def compare(due: list[Due], sam_lines, ref: np.ndarray, sc: Scoring,
            rname: str, *, repeats=None, insert=None) -> dict:
    """The numbers that decide ``correct`` (see the module docstring),
    plus ``compared`` (due reads) and ``gap_over_10`` (reads whose gap
    is over 10 points) for the record.  ``repeats``: the reference's
    repeat intervals (``locus_bad`` is read only with them); ``insert``:
    (mean, standard deviation) of a paired mix's insert size (the mate
    numbers are read only with it)."""
    prim, other = records(sam_lines)
    missing = nm_bad = locus_bad = 0
    got = np.zeros(len(due), np.int64)
    at_truth = np.zeros(len(due), bool)      # mapped over its true span
    for r, d in enumerate(due):
        fs = prim.get((d.qname, d.end), [])
        if len(fs) != 1:
            missing += 1
            continue
        f = fs[0]
        for o in other.get((d.qname, d.end), []):
            if not int(o[1]) & 0x4 and _check_record(o, d, ref, sc,
                                                     rname) is None:
                nm_bad += 1
        if int(f[1]) & 0x4:
            continue
        sr = _check_record(f, d, ref, sc, rname)
        if sr is None:
            nm_bad += 1
            continue
        got[r] = sr[0]
        L = len(d.read)
        p = int(f[3]) - 1
        at_truth[r] = p < d.pos + L and p + ref_len(f[5]) > d.pos
        if (repeats is not None and not at_truth[r]
                and repeat_free(repeats, d.pos, L)):
            locus_bad += 1
    q, t = truth_windows(due, ref)
    best = local_best(q, t, sc).astype(np.int64)
    gap = best - got
    out = {"missing": missing, "nm_mismatch": nm_bad,
           "score_gap_max": int(gap.max(initial=0))}
    if repeats is not None:
        out["locus_bad"] = locus_bad
    if insert is not None:
        out.update(_mates(due, prim, insert))
        if repeats is not None:
            out["pair_locus_bad"] = _pair_loci(due, insert, repeats, best,
                                               got, at_truth)
    out.update(compared=len(due), gap_over_10=int((gap > 10).sum()))
    return out


def _pair_loci(due: list[Due], insert, repeats, best, got,
               at_truth) -> int:
    """Pairs that bwa mem's pairing would place at their true loci and
    the run did not: one end lies outside every repeat and is reported at
    its true locus, the true insert lies within ``PROPER_IN_SD`` standard
    deviations of the mean, and the other end's best local score at its
    true locus is within ``PAIR_SCORE_SLACK`` of the score it was
    reported with (bwa pairs it there: pairing costs a proper pair at
    most 7 points within 4 standard deviations, against the 17 of
    ``-U``, and mate rescue finds that end beside its anchor).  The other
    end is then reported away from its true locus: on another repeat
    copy, or unmapped."""
    mean, sd = insert
    at = {(d.qname, d.end): r for r, d in enumerate(due)}
    bad = 0
    for (qn, end), a in at.items():
        if end != 1 or (qn, 2) not in at:
            continue
        b = at[(qn, 2)]
        da, db = due[a], due[b]
        lo = min(da.pos, db.pos)
        hi = max(da.pos + len(da.read), db.pos + len(db.read))
        if abs(hi - lo - mean) > PROPER_IN_SD * sd:
            continue
        for x, y in ((a, b), (b, a)):
            if (at_truth[x] and repeat_free(repeats, due[x].pos,
                                            len(due[x].read))
                    and not at_truth[y]
                    and best[y] >= got[y] - PAIR_SCORE_SLACK
                    and best[y] >= MIN_RESCUE_SCORE):
                bad += 1
                break
    return bad


def _mates(due: list[Due], prim: dict, insert) -> dict:
    """``mate_bad`` and ``proper_bad`` over the pairs both of whose ends
    are due and have one primary record each."""
    mate_bad = proper_bad = 0
    names = {d.qname for d in due if d.end == 1} & {
        d.qname for d in due if d.end == 2}
    for qn in sorted(names):
        f1s, f2s = prim.get((qn, 1), []), prim.get((qn, 2), [])
        if len(f1s) != 1 or len(f2s) != 1:
            continue                      # counted as missing
        f1, f2 = f1s[0], f2s[0]
        for f, m in ((f1, f2), (f2, f1)):
            if _as_written(f) != mate_fields(f, m):
                mate_bad += 1
        want = proper_expected(f1, f2, insert)
        has = (bool(int(f1[1]) & 0x2), bool(int(f2[1]) & 0x2))
        if has[0] != has[1] or (want is not None and has[0] != want):
            proper_bad += 1
    return {"mate_bad": mate_bad, "proper_bad": proper_bad}
