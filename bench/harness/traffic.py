"""The one traffic generator: reads and arrivals from a traffic file.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

* ``reads``: ``{"kind": "pairs" | "single", "read_len": L, "count": N,
  "warmup": N_warm, "simulator": {keyword arguments of the simulator}}``;
  ``count`` reads (a pair counts as two) are made for the window and
  ``warmup`` more, from other draws, for the warm-up;
* ``flags``: bwa-mem flags the cell runs with (``{"-Y": true, ...}``);
* ``arrivals`` (served cells): ``{"process": "poisson", "rate_per_s": r,
  "reads_per_request": k}``.  A window of S seconds gets round(r * S)
  requests whose gaps are the exponential distribution's quantiles at
  (i + 0.5) / n, scaled to fill the window, in an order drawn from the
  seed: every seed offers the same load, in another order.

Everything is drawn from ``--seed``: the same seed gives the same reads
and the same arrivals.
"""

from __future__ import annotations

import dataclasses
import pathlib

import numpy as np

from . import simulate
from .reference import Due

#: seed offset of the warm-up reads (any draw apart from the window's)
WARMUP_SEED_OFFSET = 1_000_003
_ASCII = np.frombuffer(b"ACGTN", np.uint8)


@dataclasses.dataclass
class Reads:
    """Reads of one set (window or warm-up), in submission order."""
    names: list
    ends: list          # [(n, L) codes] — one array, two for pairs
    due: list           # Due entries per read, in the same order


def make_reads(spec: dict, ref: np.ndarray, n_reads: int, seed: int,
               prefix: str) -> Reads:
    L = int(spec["read_len"])
    sim = dict(spec.get("simulator", {}))
    if spec["kind"] == "pairs":
        n = n_reads // 2
        r1, r2, tr = simulate.simulate_pairs(ref, n, L, seed=seed, **sim)
        names = [f"{prefix}{i}" for i in range(n)]
        due = [Due(nm, e, (r1, r2)[e - 1][i],
                   int(tr[f"pos{e}"][i]), bool(tr[f"rev{e}"][i]))
               for i, nm in enumerate(names) for e in (1, 2)]
        return Reads(names, [r1, r2], due)
    if spec["kind"] == "single":
        r, tr = simulate.simulate_reads(ref, n_reads, L, seed=seed, **sim)
        names = [f"{prefix}{i}" for i in range(n_reads)]
        due = [Due(nm, 0, r[i], int(tr["pos"][i]), bool(tr["is_rev"][i]))
               for i, nm in enumerate(names)]
        return Reads(names, [r], due)
    raise ValueError(f"unknown read kind {spec['kind']!r}")


def seq_strings(codes: np.ndarray) -> list[str]:
    """(n, L) base codes -> sequence strings (N for code 4)."""
    return [row.tobytes().decode() for row in _ASCII[np.minimum(codes, 4)]]


def write_fastq(reads: Reads, out_dir: pathlib.Path, stem: str) -> list:
    """FASTQ file(s) of a read set; pairs get ``/1`` and ``/2`` names."""
    paths = []
    paired = len(reads.ends) == 2
    for e, codes in enumerate(reads.ends, start=1):
        path = out_dir / (f"{stem}_{e}.fq" if paired else f"{stem}.fq")
        qual = "I" * codes.shape[1]
        suffix = f"/{e}" if paired else ""
        with open(path, "w") as f:
            for name, seq in zip(reads.names, seq_strings(codes)):
                f.write(f"@{name}{suffix}\n{seq}\n+\n{qual}\n")
        paths.append(path)
    return paths


def arrival_times(spec: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of the requests."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    n = max(1, round(float(spec["rate_per_s"]) * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    order = np.random.default_rng(seed).permutation(n)
    return np.cumsum(gaps[order])
