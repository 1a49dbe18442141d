"""The comparison that decides ``correct``, driven through whole runs on
the CPU at a size a test run holds: sound runs read correct, and the
cell's control and each fault the cell can have read not correct.

The runs skip the harness's look for a chip and call ``run.measure``
directly.  They run the ``batched`` engine: it is the ``pallas`` engine
with its two kernels computed by XLA instead of Pallas (byte-identical
SAM, tested in ``tests/``), and interpret-mode Pallas takes minutes per
batch on a CPU.  Each test takes tens of seconds.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import run as bench_run  # noqa: E402
from harness import spec  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
#: every cell written as data, in BENCHMARK.json or kept out of it (a
#: cell that a program fault keeps out still has checks to be tested)
ALL_CELLS = sorted(p.stem for p in (BENCH / "cells").glob("*.json"))
#: the cells whose sound runs have to read correct: all but those a
#: program fault keeps out (``kept_out.program_fault`` in the cell file)
SOUND_CELLS = [n for n in ALL_CELLS
               if not json.loads((BENCH / "cells" / f"{n}.json").read_text())
               .get("kept_out", {}).get("program_fault")]
#: window seconds per driver at the test size: one batch of 64 pairs
#: takes about 25 s on a CPU
SECONDS = {"mem": 30.0, "serve": 6.0}
SEED = 20240611


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A copy of the benchmark's data at a test size: a 300 kbp
    reference, few reads, slow arrivals."""
    root = tmp_path_factory.mktemp("bench_root")
    for d in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(BENCH / d, root / "bench" / d)
    (root / "BENCHMARK.json").write_text(json.dumps(SPEC))
    for p in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(p.read_text())
        cfg["reference"]["length_bp"] = 300_000
        cfg["engine"] = "batched"
        p.write_text(json.dumps(cfg))
    for p in (root / "bench" / "traffic").glob("*.json"):
        mix = json.loads(p.read_text())
        if "count" in mix["reads"]:
            mix["reads"]["count"] = 1024
            mix["reads"]["warmup"] = 8
        if "arrivals" in mix:
            mix["arrivals"]["rate_per_s"] = 2.0
            mix["warmup"]["widths"] = [1, 4]
            mix["clients"] = 8
        p.write_text(json.dumps(mix))
    return root


def _run(root, name, overrides=None):
    cell = spec.load_cell(name, root=root)
    ctx, numbers, _ = bench_run.measure(
        cell, SEED, SECONDS[cell.config["driver"]], False,
        overrides=overrides)
    assert ctx.attempted > 0 and numbers["compared"] > 0
    return all(numbers[k] <= lim for k, lim in cell.limits.items()), numbers


@pytest.mark.parametrize("name", SOUND_CELLS)
def test_sound_run_is_correct(small_root, name):
    ok, numbers = _run(small_root, name)
    assert ok, numbers


@pytest.mark.parametrize("name", ALL_CELLS)
def test_control_is_not_correct(small_root, name):
    control = json.loads((BENCH / "cells" / f"{name}.json").read_text())
    ok, numbers = _run(small_root, name, control["control"]["options"])
    assert not ok, numbers


@pytest.mark.parametrize("name", ALL_CELLS)
def test_half_of_each_batch_left_out_is_not_correct(small_root, name,
                                                    monkeypatch):
    from repro.api import Aligner

    def halve(orig):
        def align(self, *a, **kw):
            res = orig(self, *a, **kw)
            keep = set(res.names[:len(res.names) // 2])
            res._sam_body = [ln for ln in res._sam_body
                             if ln.split("\t", 1)[0] in keep]
            if res.alignments is not None:
                res.alignments = [al if n in keep else []
                                  for n, al in zip(res.names,
                                                   res.alignments)]
            return res
        return align

    monkeypatch.setattr(Aligner, "align", halve(Aligner.align))
    monkeypatch.setattr(Aligner, "align_pairs", halve(Aligner.align_pairs))
    ok, numbers = _run(small_root, name)
    assert not ok and numbers["missing"] > 0, numbers


PAIRED = [n for n in ALL_CELLS
          if json.loads((BENCH / "traffic" / f"{n.split('.', 1)[1]}.json")
                        .read_text())["reads"]["kind"] == "pairs"]


@pytest.mark.parametrize("name", PAIRED)
def test_a_pairing_fault_is_not_correct(small_root, name, monkeypatch):
    """Every third pair loses its proper-pair bit where PE pairing writes
    its two records."""
    from repro.pe import pairing
    orig = pairing.format_sam_pe
    calls = [0]

    def dropped(*a, proper, **kw):
        calls[0] += 1
        return orig(*a, proper=proper and (calls[0] + 1) // 2 % 3 != 0,
                    **kw)

    monkeypatch.setattr(pairing, "format_sam_pe", dropped)
    ok, numbers = _run(small_root, name)
    assert not ok and numbers["proper_bad"] > 0, numbers


@pytest.mark.parametrize("name", ALL_CELLS)
def test_an_answer_altered_where_produced_is_not_correct(small_root, name,
                                                          monkeypatch):
    from repro.core import pipeline
    orig = pipeline.finalize_alignment
    calls = [0]

    def shifted(a, *args, **kw):
        orig(a, *args, **kw)
        calls[0] += 1
        if calls[0] % 5 == 0:
            a.pos += 1

    monkeypatch.setattr(pipeline, "finalize_alignment", shifted)
    ok, numbers = _run(small_root, name)
    assert not ok and numbers["nm_mismatch"] > 0, numbers
