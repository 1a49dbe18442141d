"""CPU tests of the harness: finding cells by name, the copied simulators,
the plain reference's arithmetic, the trace reduction and the refusal of
a run without a TPU.  Run from the checkout's root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import breakdown, devtrace, kernels, reference, simulate  # noqa: E402
from harness import spec, traffic  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_is_found_by_name(name):
    cell = spec.load_cell(name)
    assert cell.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == name)
    want = {"missing", "nm_mismatch", "score_gap_max", "locus_bad"}
    if cell.traffic["reads"]["kind"] == "pairs":
        want |= {"mate_bad", "proper_bad", "pair_locus_bad"}
    if cell.config["driver"] == "serve":
        want |= {"offline_diff"}
    assert set(cell.limits) == want
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_a_cell_kept_out_of_the_benchmark_loads_by_its_name():
    names = {w["name"] for w in SPEC["workloads"]}
    kept_out = sorted(p.stem for p in (BENCH / "cells").glob("*.json")
                      if p.stem not in names)
    for name in kept_out:
        cell = spec.load_cell(name)
        assert cell.config["name"] == name.split(".", 1)[0]
    with pytest.raises(KeyError):
        spec.load_cell("chr21_mem.no_such_mix")


@pytest.mark.parametrize("name", [m["name"] for m in
                                  SPEC["end_to_end"] + SPEC["per_layer"]])
def test_every_metric_has_a_reader(name):
    assert callable(spec.metric_reader(name))


def test_a_cell_added_as_data_alone_loads(tmp_path):
    """A later PR adds a mix, a cell and a metric as files and entries."""
    shutil.copytree(BENCH / "configs", tmp_path / "bench" / "configs")
    shutil.copytree(BENCH / "traffic", tmp_path / "bench" / "traffic")
    shutil.copytree(BENCH / "cells", tmp_path / "bench" / "cells")
    shutil.copytree(BENCH / "metrics", tmp_path / "bench" / "metrics")
    mix = json.loads((BENCH / "traffic" / "poisson_se150_noN.json")
                     .read_text())
    mix["arrivals"]["rate_per_s"] = 1.0
    (tmp_path / "bench/traffic/slow_se150.json").write_text(json.dumps(mix))
    (tmp_path / "bench/cells/chr21_serve.slow_se150.json").write_text(
        json.dumps({"limits": {"missing": 0}}))
    (tmp_path / "bench/metrics/p99_latency_ms.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    spec_ = json.loads(json.dumps(SPEC))
    spec_["workloads"].append({"name": "chr21_serve.slow_se150",
                               "config": "chr21_serve",
                               "traffic": "slow_se150", "chips": 1,
                               "why": "x"})
    spec_["end_to_end"].append({"name": "p99_latency_ms", "unit": "ms",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["chr21_serve.slow_se150"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec_))
    cell = spec.load_cell("chr21_serve.slow_se150", root=tmp_path)
    assert cell.traffic["arrivals"]["rate_per_s"] == 1.0
    names = {m["name"] for m in cell.end_to_end}
    assert names == {"p99_latency_ms", "setup_s"}
    assert spec.metric_reader("p99_latency_ms",
                              bench=tmp_path / "bench")(None) == 1.0


def test_copied_simulators_match_the_program():
    from repro import data
    ref_p = data.simulate_reference(20_000, 1, seed=3, names=["c"])
    ref_b = simulate.simulate_reference(20_000, 1, seed=3, names=["c"])
    np.testing.assert_array_equal(ref_p[0][1], ref_b[0][1])
    ref = ref_b[0][1]
    kw = dict(insert_mean=400, insert_std=50, burst_frac=0.05)
    a = data.simulate_pairs(ref, 50, 150, seed=11, **kw)
    b = simulate.simulate_pairs(ref, 50, 150, seed=11, **kw)
    for x, y in zip(a[:2], b[:2]):
        np.testing.assert_array_equal(x, y)
    assert a[2].keys() == b[2].keys()
    for k in a[2]:
        np.testing.assert_array_equal(a[2][k], b[2][k])
    a = data.simulate_reads(ref, 50, 100, seed=12)
    b = simulate.simulate_reads(ref, 50, 100, seed=12)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1]["pos"], b[1]["pos"])


def test_arrivals_fill_the_window_at_the_rate_in_another_order():
    spec_ = {"process": "poisson", "rate_per_s": 5.0}
    a = traffic.arrival_times(spec_, 50.0, 1)
    b = traffic.arrival_times(spec_, 50.0, 2**31 + 7)
    assert len(a) == len(b) == 250
    assert a[-1] == pytest.approx(50.0) and b[-1] == pytest.approx(50.0)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                               np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)


def _local_best_scalar(q, t, sc):
    """Textbook Gotoh local alignment, one cell at a time."""
    neg = -(1 << 28)
    n, m = len(q), len(t)
    H = [[0] * (m + 1) for _ in range(n + 1)]
    E = [[neg] * (m + 1) for _ in range(n + 1)]
    F = [[neg] * (m + 1) for _ in range(n + 1)]
    best = 0
    for i in range(1, n + 1):
        for j in range(1, m + 1):
            a, b = q[i - 1], t[j - 1]
            s = (-10_000 if b == reference.PAD else
                 sc.ambiguous if a > 3 or b > 3 else
                 sc.match if a == b else -sc.mismatch)
            E[i][j] = max(E[i][j - 1] - sc.gap_extend,
                          H[i][j - 1] - sc.gap_open - sc.gap_extend)
            F[i][j] = max(F[i - 1][j] - sc.gap_extend,
                          H[i - 1][j] - sc.gap_open - sc.gap_extend)
            H[i][j] = max(0, H[i - 1][j - 1] + s, E[i][j], F[i][j])
            best = max(best, H[i][j])
    return best


def test_local_best_matches_the_textbook_recurrence():
    rng = np.random.default_rng(5)
    sc = reference.Scoring()
    for trial in range(6):
        t = rng.integers(0, 4, 40).astype(np.uint8)
        q = t[5:30].copy()
        q[rng.integers(0, len(q), 3)] = rng.integers(0, 5, 3)
        if trial % 2:
            q = np.delete(q, [7, 8, 9])           # a deletion in the read
        else:
            q = np.insert(q, 12, [1, 2])          # an insertion
        t[-3:] = reference.PAD
        got = reference.local_best(q[None], t[None], sc)[0]
        assert got == _local_best_scalar(q.tolist(), t.tolist(), sc)


def test_score_record_walks_the_cigar():
    sc = reference.Scoring()
    ref = np.array([0, 1, 2, 3, 0, 1, 2, 3, 0, 1], np.uint8)
    read = np.array([3, 1, 2, 0, 3, 0, 4], np.uint8)   # S, 2M, 1I, 3M(1 N)
    # 1S 2M 1I 3M at ref 1: M 1,2 vs ref 1,2; I base 0; M 3,0,N vs 3,0,1
    assert reference.score_record(read, ref, 1, "1S2M1I3M", sc) == (
        2 - 7 + 2 - 1, 2)
    assert reference.score_record(read, ref, 1, "2S2M1D2M", sc) is None
    assert reference.score_record(read, ref, 8, "7M", sc) is None


def test_compare_counts_missing_bad_nm_and_gaps():
    rng = np.random.default_rng(9)
    ref = rng.integers(0, 4, 2000).astype(np.uint8)
    sc = reference.Scoring()
    reads = [ref[100:150].copy(), ref[400:450].copy(), ref[900:950].copy()]
    due = [reference.Due(f"r{i}", 0, r, p, False)
           for i, (r, p) in enumerate(zip(reads, (100, 400, 900)))]
    good = [f"r0\t0\tc\t101\t60\t50M\t*\t0\t0\t*\t*\tAS:i:50\tNM:i:0",
            f"r1\t0\tc\t401\t60\t50M\t*\t0\t0\t*\t*\tAS:i:50\tNM:i:0",
            f"r2\t0\tc\t901\t60\t50M\t*\t0\t0\t*\t*\tAS:i:50\tNM:i:0"]
    out = reference.compare(due, good, ref, sc, "c")
    assert (out["missing"], out["nm_mismatch"], out["score_gap_max"]) == (
        0, 0, 0)
    bad = [good[0], good[1].replace("NM:i:0", "NM:i:1"),
           good[2].replace("\t901\t", "\t911\t")]
    out = reference.compare(due, bad, ref, sc, "c")
    assert out["nm_mismatch"] == 2 and out["score_gap_max"] == 50
    out = reference.compare(due, good[:2], ref, sc, "c")
    assert out["missing"] == 1


def _rec(flag, pos, cigar, rnext="*", pnext=0, tlen=0):
    rname = "c" if pos else "*"
    return ["q", str(flag), rname, str(pos), "60", cigar, rnext,
            str(pnext), str(tlen), "*", "*"]


def test_mate_fields_follow_bwa_mem_aln2sam():
    # FR pair: end 1 forward at 101 (50M), end 2 reverse at 401 (48M2D)
    f1 = _rec(0x1 | 0x2 | 0x20 | 0x40, 101, "50M", "=", 401, 350)
    f2 = _rec(0x1 | 0x2 | 0x10 | 0x80, 401, "48M2D", "=", 101, -350)
    assert reference.mate_fields(f1, f2) == (0x21, "c", 101, "=", 401, 350)
    assert reference.mate_fields(f2, f1) == (0x11, "c", 401, "=", 101, -350)
    assert reference.proper_expected(f1, f2, (400, 50)) is True
    assert reference.proper_expected(f1, f2, (800, 40)) is False
    assert reference.proper_expected(f1, f2, (600, 40)) is None
    # end 2 unmapped: it takes end 1's place and strand (0x10), end 1
    # lends its strand as the mate's (0x20), as bwa's 121/181 pair does
    m1 = _rec(0x1 | 0x8 | 0x10 | 0x20 | 0x40, 101, "50M", "=", 101, 0)
    m2 = _rec(0x1 | 0x4 | 0x10 | 0x20 | 0x80, 101, "*", "=", 101, 0)
    assert reference.mate_fields(m1, m2) == (0x39, "c", 101, "=", 101, 0)
    assert reference.mate_fields(m2, m1) == (0x31, "c", 101, "=", 101, 0)
    assert reference.proper_expected(m1, m2, (400, 50)) is False
    # both unmapped
    u = _rec(0x1 | 0x4 | 0x8 | 0x40, 0, "*")
    assert reference.mate_fields(u, u) == (0x9, "*", 0, "*", 0, 0)
    # RF: the reverse end lies left of the forward one
    r1 = _rec(0x1 | 0x20 | 0x40, 401, "50M", "=", 101, -251)
    r2 = _rec(0x1 | 0x10 | 0x80, 101, "50M", "=", 401, 251)
    assert reference.mate_fields(r1, r2)[5] < 0
    assert reference.proper_expected(r1, r2, (250, 50)) is False


def test_repeat_intervals_merge_and_bound_the_locus_check():
    starts, ends = simulate.repeat_intervals([(100, 1000), (250, 5000)], 200)
    assert starts.tolist() == [100, 1000, 5000]
    assert ends.tolist() == [450, 1200, 5200]
    m = reference.LOCUS_MARGIN
    assert reference.repeat_free((starts, ends), 450 + m, 100)
    assert not reference.repeat_free((starts, ends), 450 + m - 1, 100)
    assert not reference.repeat_free((starts, ends), 2000, 3001 - m)
    assert reference.repeat_free((starts, ends), 2000, 3000 - m)
    pastes = []
    ref = simulate.make_reference(5000, seed=4, pastes=pastes)
    np.testing.assert_array_equal(ref, simulate.make_reference(5000, seed=4))
    assert len(pastes) == int(5000 * 0.3 / 200)


def test_compare_counts_mate_proper_and_locus_faults():
    rng = np.random.default_rng(10)
    ref = rng.integers(0, 4, 3000).astype(np.uint8)
    sc = reference.Scoring()
    r1 = ref[100:150].copy()
    r2 = simulate.revcomp_read(ref[450:500])
    due = [reference.Due("p", 1, r1, 100, False),
           reference.Due("p", 2, r2, 450, True)]
    good = ["p\t99\tc\t101\t60\t50M\t=\t451\t400\t*\t*\tNM:i:0",
            "p\t147\tc\t451\t60\t50M\t=\t101\t-400\t*\t*\tNM:i:0"]
    repeats = (np.array([2000]), np.array([2200]))
    out = reference.compare(due, good, ref, sc, "c", repeats=repeats,
                            insert=(400, 50))
    assert (out["locus_bad"], out["mate_bad"], out["proper_bad"],
            out["pair_locus_bad"]) == (0, 0, 0, 0)
    bad = [good[0].replace("\t451\t400", "\t452\t400"),
           good[1].replace("\t147\t", "\t145\t")]
    out = reference.compare(due, bad, ref, sc, "c", repeats=repeats,
                            insert=(400, 50))
    assert (out["mate_bad"], out["proper_bad"]) == (1, 1)
    # end 1 reported at an equal-scoring copy of its bases far away
    ref2 = ref.copy()
    ref2[2600:2650] = ref2[100:150]
    moved = [good[0].replace("\t101\t", "\t2601\t"), good[1]]
    out = reference.compare(due, moved, ref2, sc, "c", repeats=repeats,
                            insert=(400, 50))
    assert out["locus_bad"] == 1 and out["score_gap_max"] == 0
    assert out["pair_locus_bad"] == 1
    assert out["mate_bad"] == 2          # PNEXT and TLEN of both ends


def _planes():
    """A hand-made trace: the window starts at 1000 ns and lasts 1 us;
    device ops [1100, 1300) and [1250, 1400) overlap, [1600, 1700) is
    the occ kernel, [2100, 2200) lies after the window."""
    host = ("/host:CPU", [("python", [("bench.window", 1000, 5)])])
    dev = ("/device:TPU:0", [
        ("XLA Modules", [("jit_f", 1100, 300)]),
        ("XLA Ops", [("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)", 1100,
                      200), ("fusion.2", 1250, 150),
                     ("_occ_kernel_body", 1600, 100),
                     ("fusion.3", 2100, 100)])])
    return [host, dev]


def test_device_trace_reduction_by_hand():
    dt = devtrace.reduce_planes(_planes(), "bench.window", 1e-6)
    assert dt.window == (1000, 2000)
    assert dt.busy_s() == pytest.approx(400e-9)     # 300 + 100
    assert dt.gaps() == [(1000, 1100), (1400, 1600), (1700, 2000)]
    assert dt.time_within([(1550, 1720)]) == pytest.approx(100e-9)
    assert dt.time_within([(1000, 2000)], "fusion") == pytest.approx(350e-9)
    assert dt.time_within([(1000, 2000)], "_occ") == pytest.approx(100e-9)
    spans = [("smem", 1050, 1500, {}), ("kernel.fmocc", 1450, 1700,
                                        {"tasks": 10}),
             ("finalize", 1700, 2000, {})]
    idle = breakdown.idle_by_host_span(dt.gaps(), spans)
    assert idle == pytest.approx({"smem": 100e-9, "kernel.fmocc": 200e-9,
                                  "finalize": 300e-9})
    assert kernels.fmocc_floor_bytes(spans) == 10 * 8 * 4


def test_run_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        SPEC["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


DATA = BENCH / "tests" / "data"


def test_device_trace_reduction_of_a_chip_trace():
    """A trace recorded on a v5e by ``record_trace.py``: the reduction's
    busy time, idle gaps and kernel time against a plain walk over the
    same events."""
    from jax.profiler import ProfileData
    meta = json.loads((DATA / "small.spans.json").read_text())
    seconds = meta["seconds"]
    spans = [tuple(s) for s in meta["spans"]]
    dt = devtrace.load(DATA / "small.xplane.pb", "bench.window", seconds)
    pd = ProfileData.from_file(str(DATA / "small.xplane.pb"))
    start = min(int(e.start_ns) for pl in pd.planes
                if not pl.name.startswith("/device:")
                for ln in pl.lines for e in ln.events
                if e.name == "bench.window")
    end = start + seconds * 1e9
    ops = sorted((max(int(e.start_ns), start),
                  min(int(e.start_ns) + int(e.duration_ns), end),
                  devtrace.op_name(e.name))
                 for pl in pd.planes if pl.name == "/device:TPU:0"
                 for ln in pl.lines if ln.name == "XLA Ops"
                 for e in ln.events
                 if int(e.start_ns) + int(e.duration_ns) > start
                 and int(e.start_ns) < end)
    busy, at = 0, start
    for s, e, _ in ops:                 # the union, one op at a time
        if e > at:
            busy += e - max(s, at)
            at = e
    assert len(dt.ops) == 1 and len(dt.ops[0]) == len(ops) > 0
    assert dt.busy_s() == pytest.approx(busy / 1e9, abs=1e-9)
    gaps = dt.gaps()
    assert sum(e - s for s, e in gaps) / 1e9 == pytest.approx(
        dt.window_s - dt.busy_s(), abs=1e-9)
    idle = breakdown.idle_by_host_span(gaps, spans)
    assert sum(idle.values()) == pytest.approx(dt.window_s - dt.busy_s(),
                                               abs=1e-9)
    for name in ("kernel.fmocc", "kernel.bsw_pallas"):
        iv = [(s, e) for n, s, e, _ in spans if n == name]
        want = sum(e - s for s, e, _ in ops
                   if any(a <= (s + e) / 2 <= b for a, b in iv))
        assert dt.time_within(iv) == pytest.approx(want / 1e9, abs=1e-9)
    bsw = kernels.bsw_device_s(dt, spans)
    assert 0 < bsw <= dt.time_within(
        [(s, e) for n, s, e, _ in spans if n == "kernel.bsw_pallas"])
    # the numbers of this recording (8 reads of 100 bp, 2 Mbp reference,
    # one v5e): 438 occ dispatches, two BSW dispatches
    assert len(ops) == 30482 and len(gaps) == 23992
    assert dt.busy_s() == pytest.approx(0.059173409, abs=1e-9)
    assert dt.window_s == pytest.approx(2.507455184, abs=1e-9)
    assert kernels.fmocc_device_s(dt, spans) == pytest.approx(0.057836029,
                                                              abs=1e-9)
    assert bsw == pytest.approx(0.00059735, abs=1e-9)
    assert kernels.fmocc_floor_bytes(spans) == 99552
    assert max(idle, key=idle.get) == "kernel.fmocc"
    assert idle["kernel.fmocc"] == pytest.approx(1.534652822, abs=1e-6)
