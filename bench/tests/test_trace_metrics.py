"""CPU tests of the per-layer metrics that read the program's request
lifecycle, finalize and compile spans: each reader on a hand-built
``RunContext`` with a known answer, and None where there is nothing to
read.  Run from the checkout's root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402
from harness.context import RunContext  # noqa: E402

MS = 1_000_000                      # nanoseconds in a millisecond

SERVE = ("queue_wait_ms_p95.serve", "engine_ms_per_batch.serve",
         "batch_host_ms_per_batch.serve", "compile_ms_in_window.serve")
MEM = ("finalize_cigar_ms_per_kread.mem", "finalize_replay_ms_per_kread.mem",
       "cigar_cells_per_read.mem", "compile_ms_in_window.mem")


def _served() -> RunContext:
    """Two engine batches: 20 requests waiting 1..20 ms; batch 0 of
    100 ms holds a 70 ms engine run, batch 1 of 50 ms one of 20 ms; two
    compiles of 3 and 4.5 ms; batch 2 begins at the window's close and
    its engine run after it, so only its batch span is in the window."""
    spans = [("serve.queue_wait", 0, k * MS, {"rid": f"q{k}", "batch": 0})
             for k in range(1, 21)]
    spans += [
        ("serve.batch", 20 * MS, 120 * MS, {"batch": 0}),
        ("serve.engine", 25 * MS, 95 * MS, {"batch": 0}),
        ("compile", 30 * MS, 33 * MS, {"fun": "jit(sal_direct)"}),
        ("serve.sam", 95 * MS, 100 * MS, {"batch": 0}),
        ("serve.batch", 130 * MS, 180 * MS, {"batch": 1}),
        ("serve.engine", 140 * MS, 160 * MS, {"batch": 1}),
        ("compile", 141 * MS, 145.5 * MS, {"fun": "jit(bsw_pallas_call)"}),
        ("serve.batch", 199 * MS, 400 * MS, {"batch": 2}),
    ]
    return RunContext(seconds=0.2, host_spans=spans)


def _offline() -> RunContext:
    snap = {"time_finalize_s": 30.0, "time_finalize.cigar_s": 24.0,
            "time_finalize.replay_s": 1.5, "finalize_cigar_cells": 2_500_000,
            "finalize_alignments": 900}
    spans = [("finalize", 0, 900 * MS, {}),
             ("finalize.replay", 0, 40 * MS, {}),
             ("compile", 950 * MS, 952.5 * MS, {"fun": "jit(sal_direct)"})]
    return RunContext(seconds=51, snapshot=snap, reads_traced=800,
                      host_spans=spans)


@pytest.mark.parametrize("name, want", [
    ("queue_wait_ms_p95.serve", 19.0),      # nearest rank: 19th of 20
    ("engine_ms_per_batch.serve", 45.0),    # (70 + 20) / 2
    ("batch_host_ms_per_batch.serve", 30.0),  # (30 + 30) / 2; batch 2 out
    ("compile_ms_in_window.serve", 7.5),
    ("finalize_cigar_ms_per_kread.mem", 30_000.0),
    ("finalize_replay_ms_per_kread.mem", 1_875.0),
    ("cigar_cells_per_read.mem", 3_125.0),
    ("compile_ms_in_window.mem", 2.5),
])
def test_reader_gives_the_known_answer(name, want):
    ctx = _served() if name.endswith(".serve") else _offline()
    assert spec.metric_reader(name)(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", SERVE + MEM)
def test_reader_gives_none_on_an_empty_run(name):
    assert spec.metric_reader(name)(RunContext(seconds=51)) is None


@pytest.mark.parametrize("name", MEM)
def test_mem_reader_gives_none_without_the_new_spans(name):
    """A program without finalize's parts: ``finalize`` alone."""
    ctx = RunContext(seconds=51, reads_traced=800,
                     snapshot={"time_finalize_s": 30.0},
                     host_spans=[("finalize", 0, 900 * MS, {})])
    assert spec.metric_reader(name)(ctx) is None


@pytest.mark.parametrize("marker", ["serve.batch", "finalize.replay"])
def test_compile_reads_zero_when_the_window_compiled_nothing(marker):
    ctx = RunContext(seconds=51, host_spans=[(marker, 0, 5 * MS, {})])
    name = ("compile_ms_in_window.serve" if marker == "serve.batch"
            else "compile_ms_in_window.mem")
    assert spec.metric_reader(name)(ctx) == 0.0
