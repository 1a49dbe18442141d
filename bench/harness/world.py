"""The configuration's reference and its index, built once per checkout.

Users map many read sets against one ``bwa index``, so the reference is
part of the configuration: its length, repeat model and seed are in the
config file, and ``--seed`` never changes it.  The first run of a cell in
a checkout simulates the reference, writes it as FASTA and builds the
program's index bundle (``repro.cli index``) under
``bench/.cache/ref-<hash of the reference's parameters>/``; later runs
load it from there.  The reference's bases are kept beside it as ``.npy``
for the plain reference check, which reads nothing the program made.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import pathlib
import shutil
import sys

import numpy as np

from . import simulate
from .spec import BENCH

CACHE = BENCH / ".cache"
_ASCII = np.frombuffer(b"ACGTN", np.uint8)


def _write_fasta(path: pathlib.Path, contigs, width: int = 60) -> None:
    with open(path, "wb") as f:
        for name, codes in contigs:
            f.write(f">{name}\n".encode())
            seq = _ASCII[np.minimum(codes, 4)]
            for i in range(0, len(seq), width * 4096):
                block = seq[i:i + width * 4096]
                rows = [block[j:j + width].tobytes()
                        for j in range(0, len(block), width)]
                f.write(b"\n".join(rows) + b"\n")


def reference_dir(params: dict) -> pathlib.Path:
    key = hashlib.sha256(json.dumps(params, sort_keys=True).encode())
    return CACHE / f"ref-{key.hexdigest()[:16]}"


def _simulate(params: dict) -> tuple[np.ndarray, tuple]:
    """The reference's codes and its repeat intervals (one contig: the
    simulator's first contig is ``make_reference`` at the seed itself)."""
    pastes: list = []
    ref = simulate.make_reference(
        int(params["length_bp"]), seed=int(params["seed"]),
        repeat_frac=float(params["repeat_frac"]),
        repeat_len=int(params["repeat_len"]), pastes=pastes)
    return ref, simulate.repeat_intervals(pastes, int(params["repeat_len"]))


def load_repeats(params: dict) -> tuple:
    """(starts, ends) of the reference's repeat intervals."""
    r = np.load(reference_dir(params) / "repeats.npy")
    return r[0], r[1]


def ensure_reference(params: dict) -> tuple[pathlib.Path, np.ndarray, bool]:
    """(FASTA path, which is the index prefix; reference codes; whether
    this call built it).  Single-contig references only: the plain
    reference check reads positions on one contig."""
    d = reference_dir(params)
    fa = d / "ref.fa"
    built = False
    if not ((d / "done").exists() and (d / "repeats.npy").exists()):
        shutil.rmtree(d, ignore_errors=True)
        d.mkdir(parents=True)
        ref, (starts, ends) = _simulate(params)
        contigs = [(params["name"], ref)]
        np.save(d / "ref.npy", ref)
        np.save(d / "repeats.npy", np.stack([starts, ends]))
        _write_fasta(fa, contigs)
        from repro import cli
        with contextlib.redirect_stdout(sys.stderr):
            rc = cli.main(["index", str(fa)])
        if rc != 0:
            raise RuntimeError(f"repro.cli index exited {rc}")
        (d / "done").write_text("ok\n")
        built = True
    return fa, np.load(d / "ref.npy"), built
