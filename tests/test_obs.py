"""The repro.obs telemetry subsystem (PR 6).

Covers the observability contract:

* Snapshot merge semantics — numerics sum, Gauges max, Hists
  bucket-merge, non-numerics collect into MultiValue — and merge
  ASSOCIATIVITY across arbitrary groupings (the property that makes
  per-shard profiles sum deterministically);
* JSON round-trip of the --profile artifact (Gauge/Hist/MultiValue
  tagged encodings survive);
* span(): NULL_SPAN identity when telemetry is off, stage-timer keys +
  Chrome trace events when on, nesting/containment in the trace;
* TraceCollector: trace-event schema chrome://tracing/Perfetto accept,
  bounded buffer, thread ids;
* report: every pipeline stage rendered (observed or not), breakdown
  percentages, profile write/read round-trip;
* facade neutrality: with telemetry ON, SE and PE SAM stays
  byte-identical to telemetry OFF for BOTH stock engines, and
  BatchResult.stats keeps full dict compatibility;
* dist/ft wiring: align_shard reports shard wall time and feeds a
  StragglerMonitor via the new observe() entry point.
"""

import json

import numpy as np
import pytest

from repro import obs
from repro.api import Aligner, AlignOptions
from repro.core import fmindex as fmx
from repro.data import make_reference, simulate_pairs, simulate_reads
from repro.ft import StragglerMonitor
from repro.io.fastq import FastqRecord, write_fastq
from repro.obs.metrics import Gauge, Hist, MultiValue, Snapshot


@pytest.fixture(scope="module")
def world():
    ref = make_reference(20000, seed=7)
    idx = fmx.build_index(ref)
    reads, _ = simulate_reads(ref, 12, 101, seed=3)
    return idx, reads


@pytest.fixture(scope="module")
def pe_world():
    ref = make_reference(30000, seed=5)
    idx = fmx.build_index(ref)
    r1, r2, _ = simulate_pairs(ref, 16, 101, insert_mean=300, insert_std=30,
                               seed=9, burst_frac=0.25)
    return idx, r1, r2


# ---------------------------------------------------------------------
# Snapshot merge semantics
# ---------------------------------------------------------------------

def test_merge_numeric_sum_gauge_max():
    a = Snapshot(n=3, t=0.5, g=Gauge(2.0))
    b = Snapshot(n=4, t=0.25, g=Gauge(7.0), only_b="x")
    m = a.merge(b)
    assert m["n"] == 7 and m["t"] == 0.75
    assert isinstance(m["g"], Gauge) and m["g"] == 7.0
    assert m["only_b"] == "x"
    # merge() leaves operands untouched
    assert a["n"] == 3 and b["n"] == 4


def test_merge_nonnumeric_collects_multivalue():
    a = Snapshot(pes=[True, False])
    b = Snapshot(pes=[True])
    c = Snapshot(pes=[False])
    m = Snapshot.merge_all([a, b, c])
    assert isinstance(m["pes"], MultiValue)
    assert list(m["pes"]) == [[True, False], [True], [False]]


def test_merge_associative():
    def part(i):
        h = Hist.new((1.0, 10.0, 100.0))
        for v in (0.5 * i, 5.0, 50.0 + i):
            h.observe(v)
        return Snapshot(n=i, g=Gauge(i), h=h, tag=f"p{i}")

    a, b, c = part(1), part(2), part(3)
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    assert set(left) == set(right)
    assert left["n"] == right["n"] == 6
    assert left["g"] == right["g"] == 3.0
    assert left["h"].counts == right["h"].counts
    assert left["h"].count == right["h"].count == 9
    assert list(left["tag"]) == list(right["tag"]) == ["p1", "p2", "p3"]


def test_hist_observe_and_edge_mismatch():
    h = Hist.new((1.0, 10.0))
    for v in (0.5, 1.0, 5.0, 100.0):
        h.observe(v)
    assert h.counts == [2, 1, 1]          # <=1, (1,10], >10
    assert h.count == 4 and h.vmin == 0.5 and h.vmax == 100.0
    assert h.mean == pytest.approx((0.5 + 1 + 5 + 100) / 4)
    with pytest.raises(ValueError, match="different edges"):
        h.merge(Hist.new((1.0, 20.0)))
    with pytest.raises(ValueError, match="strictly"):
        Hist.new((3.0, 1.0))


def test_snapshot_json_roundtrip():
    h = Hist.new((1.0, 10.0))
    h.observe(3.0)
    s = Snapshot(n=5, t=0.125, g=Gauge(4.0), h=h,
                 mv=MultiValue([{"mu": 300.0}, {"mu": 310.0}]),
                 ni=np.int64(9), nf=np.float32(0.5))
    back = Snapshot.from_jsonable(json.loads(json.dumps(s.to_jsonable())))
    assert back["n"] == 5 and back["t"] == 0.125
    assert isinstance(back["g"], Gauge) and back["g"] == 4.0
    assert isinstance(back["h"], Hist) and back["h"].counts == h.counts
    assert isinstance(back["mv"], MultiValue) and len(back["mv"]) == 2
    assert back["ni"] == 9 and back["nf"] == 0.5
    # round-tripped parts still merge
    assert back.merge(back)["n"] == 10


# ---------------------------------------------------------------------
# spans / ambient context
# ---------------------------------------------------------------------

def test_span_is_noop_when_off():
    assert not obs.enabled()
    assert obs.span("smem") is obs.NULL_SPAN
    assert obs.span("bsw", cat="kernel", lanes=8) is obs.NULL_SPAN
    # helpers silently no-op too
    obs.count("x")
    obs.observe("y", 1.0)
    obs.set_gauge("z", 2.0)


def test_span_records_time_and_counters():
    reg = obs.MetricsRegistry()
    with obs.activate(reg):
        assert obs.enabled()
        with obs.span("smem"):
            obs.count("smem_rounds", 3)
        obs.observe("lanes", 64)
        obs.set_gauge("groups", 2)
    assert not obs.enabled()
    snap = reg.snapshot()
    assert snap["time_smem_s"] >= 0.0
    assert snap["smem_rounds"] == 3
    assert isinstance(snap["lanes"], Hist) and snap["lanes"].count == 1
    assert isinstance(snap["groups"], Gauge) and snap["groups"] == 2.0


def test_activate_nests_and_restores():
    outer, inner = obs.MetricsRegistry(), obs.MetricsRegistry()
    with obs.activate(outer):
        obs.count("k")
        with obs.activate(inner):
            obs.count("k", 10)
        obs.count("k")
    assert outer.snapshot()["k"] == 2
    assert inner.snapshot()["k"] == 10


def test_trace_nesting_and_schema(tmp_path):
    tel = obs.Telemetry(trace=True)
    with tel.activate():
        with obs.span("outer", reads=4):
            with obs.span("inner.a", cat="kernel"):
                pass
            with obs.span("inner.b"):
                pass
    evs = tel.tracer.to_dict()["traceEvents"]
    by = {e["name"]: e for e in evs}
    assert set(by) == {"outer", "inner.a", "inner.b"}
    # children close before the parent -> appear first; parent contains both
    assert [e["name"] for e in evs] == ["inner.a", "inner.b", "outer"]
    o, a, b2 = by["outer"], by["inner.a"], by["inner.b"]
    for child in (a, b2):
        assert o["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= o["ts"] + o["dur"] + 1e-3
    assert a["ts"] + a["dur"] <= b2["ts"] + 1e-3     # ordering
    # Chrome trace-event schema
    for e in evs:
        assert e["ph"] == "X" and isinstance(e["ts"], float)
        assert e["dur"] >= 0 and "pid" in e and "tid" in e
        assert isinstance(e["cat"], str)
    assert a["cat"] == "kernel" and o["args"] == {"reads": 4}
    # save() emits chrome://tracing-loadable JSON
    p = tmp_path / "t.trace.json"
    tel.tracer.save(p)
    loaded = json.loads(p.read_text())
    assert loaded["displayTimeUnit"] == "ms"
    assert len(loaded["traceEvents"]) == 3


def test_trace_collector_bounded():
    tc = obs.TraceCollector(max_events=2)
    for i in range(5):
        tc.complete(f"e{i}", 0.0, 0.1)
    assert len(tc) == 2
    assert tc.to_dict()["otherData"]["dropped"] == 3


# ---------------------------------------------------------------------
# report / profile artifact
# ---------------------------------------------------------------------

def test_report_names_every_stage():
    snap = Snapshot(time_smem_s=0.5, time_bsw_s=1.0, sa_lookups=100,
                    bsw_tasks=7, cells_useful=40, cells_total=100)
    text = obs.render(snap, wall_s=2.0)
    for _, label in obs.STAGES:
        assert label in text
    assert "unattributed" in text
    assert "40.0%" in text                 # cell efficiency
    b = obs.breakdown(snap, wall_s=2.0)
    rows = {r["stage"]: r for r in b["stages"]}
    assert rows["bsw"]["pct_wall"] == 50.0
    assert rows["bsw"]["pct_measured"] == pytest.approx(100 * 1.0 / 1.5,
                                                        abs=0.01)
    assert rows["sal"]["time_s"] == 0.0    # unobserved stages still listed
    assert b["unattributed_s"] == pytest.approx(0.5)
    assert b["counters"]["sa_lookups"] == 100
    assert b["efficiency"]["bsw"]["ratio"] == 0.4


def test_profile_write_read_roundtrip(tmp_path):
    h = Hist.new(obs.RATIO_EDGES)
    h.observe(0.12)
    snap = Snapshot(time_smem_s=0.25, sa_lookups=42, io_pad_frac=h,
                    n_length_groups=Gauge(2))
    p = tmp_path / "prof.json"
    obs.write_profile(p, snap, wall_s=1.5, meta={"engine": "batched"})
    payload = obs.read_profile(p)
    assert payload["wall_s"] == 1.5 and payload["meta"]["engine"] == "batched"
    back = payload["snapshot"]
    assert isinstance(back, Snapshot) and back["sa_lookups"] == 42
    assert isinstance(back["io_pad_frac"], Hist)
    assert isinstance(back["n_length_groups"], Gauge)
    assert "batch pad waste" in obs.render(back, wall_s=payload["wall_s"])
    # version guard
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 99, "snapshot": {}}))
    with pytest.raises(ValueError, match="version"):
        obs.read_profile(bad)


# ---------------------------------------------------------------------
# facade: telemetry must not change output, stats stay dict-compatible
# ---------------------------------------------------------------------

def test_se_sam_identical_with_telemetry(world):
    idx, reads = world
    for engine in ("batched", "baseline"):
        plain = Aligner.from_index(idx, AlignOptions(engine=engine))
        tele = Aligner.from_index(idx, AlignOptions(engine=engine),
                                  telemetry=obs.Telemetry(trace=True))
        res_p, res_t = plain.align(reads), tele.align(reads)
        assert res_t.sam() == res_p.sam()
        # telemetry-on stats gained stage timers + counters
        assert res_t.stats["time_smem_s"] > 0.0
        assert res_t.stats["time_bsw_s"] > 0.0
        assert res_t.stats["sa_lookups"] == res_p.stats["sa_lookups"]
        assert res_t.stats["bsw_tasks"] == res_p.stats["bsw_tasks"]


def test_pe_sam_identical_with_telemetry(pe_world):
    idx, r1, r2 = pe_world
    for engine in ("batched", "baseline"):
        plain = Aligner.from_index(idx, AlignOptions(engine=engine))
        tele = Aligner.from_index(idx, AlignOptions(engine=engine),
                                  telemetry=True)
        res_p, res_t = plain.align_pairs(r1, r2), tele.align_pairs(r1, r2)
        assert res_t.sam() == res_p.sam()
        for key in ("time_smem_s", "time_bsw_s", "time_pe_pair_s"):
            assert res_t.stats[key] > 0.0


def test_stats_dict_compatible(world):
    idx, reads = world
    res = Aligner.from_index(idx, telemetry=True).align(reads)
    assert isinstance(res.stats, Snapshot) and isinstance(res.stats, dict)
    assert res.stats["bsw_tasks"] > 0
    assert res.stats["n_length_groups"] == 1      # Gauge ==-compatible
    d = dict(res.stats)                           # plain-dict consumers
    assert d["bsw_tasks"] == res.stats["bsw_tasks"]
    assert json.dumps(res.stats.to_jsonable())    # profile-serializable
    # trace spans name the batched pipeline stages
    tele = obs.Telemetry(trace=True)
    Aligner.from_index(idx, telemetry=tele).align(reads)
    names = {e["name"] for e in tele.tracer.to_dict()["traceEvents"]}
    assert {"smem", "sal", "chain", "bsw", "finalize"} <= names


def test_stream_sam_counts_io(tmp_path, world):
    idx, reads = world
    fq = tmp_path / "r.fq"
    write_fastq(fq, [FastqRecord(f"read{i}",
                                 "".join("ACGTN"[b] for b in row), None)
                     for i, row in enumerate(reads)])
    from repro.io.stream import open_batches
    al = Aligner.from_index(idx, telemetry=True)
    out = tmp_path / "o.sam"
    summary = al.stream_sam(open_batches(str(fq), batch_size=8), str(out))
    assert summary["n_reads"] == len(reads)
    st = summary["stats"]
    assert st["io_batches"] == 2 and st["io_reads"] == len(reads)
    assert st["time_io_s"] > 0.0
    assert isinstance(st["io_pad_frac"], Hist)
    assert st["io_pad_frac"].count == 2
    # telemetry-off stream produces the identical SAM
    plain = Aligner.from_index(idx)
    out2 = tmp_path / "o2.sam"
    plain.stream_sam(open_batches(str(fq), batch_size=8), str(out2))
    assert out.read_text() == out2.read_text()


# ---------------------------------------------------------------------
# dist / ft wiring
# ---------------------------------------------------------------------

def test_align_shard_wall_time_and_straggler(tmp_path, world):
    from repro.dist.api import align_shard
    idx, reads = world
    fq = tmp_path / "r.fq"
    write_fastq(fq, [FastqRecord(f"read{i}",
                                 "".join("ACGTN"[b] for b in row), None)
                     for i, row in enumerate(reads)])
    al = Aligner.from_index(idx, telemetry=True)
    mon = StragglerMonitor(window=8)
    s0 = align_shard(al, str(fq), out=str(tmp_path / "s0.sam"),
                     spec="0/2", monitor=mon, step=0)
    s1 = align_shard(al, str(fq), out=str(tmp_path / "s1.sam"),
                     spec="1/2", monitor=mon, step=1)
    assert s0["shard"] == (0, 2) and s1["shard"] == (1, 2)
    assert s0["wall_s"] > 0.0 and "straggler" in s0
    assert s0["n_reads"] + s1["n_reads"] == len(reads)
    # per-shard Snapshots merge into one run-wide profile
    merged = Snapshot.merge_all([s0["stats"], s1["stats"]])
    assert merged["io_reads"] == len(reads)
    assert merged["time_smem_s"] >= max(s0["stats"]["time_smem_s"],
                                        s1["stats"]["time_smem_s"])


def test_straggler_observe_external_times():
    mon = StragglerMonitor(window=16, threshold=1.5, persist=2)
    ev = None
    for i in range(12):
        ev = mon.observe(i, host=0,
                         step_time=0.02 if i < 10 else 0.08) or ev
    assert ev is not None and ev.action in ("rebalance", "checkpoint")
    assert ev.step_time == pytest.approx(0.08)


# ---------------------------------------------------------------------
# explicit spans, finalize's parts, compiles, profiler annotations
# ---------------------------------------------------------------------

def test_record_lands_on_registry_and_tracer():
    obs.record("gap", 1.0, 3.5, cat="serve", rid="r1")   # off: no-op
    tel = obs.Telemetry(trace=True)
    with tel.activate() as reg:
        t0 = tel.tracer._epoch + 0.25
        obs.record("gap", t0, t0 + 0.5, cat="serve", rid="r1")
    assert reg.snapshot()["time_gap_s"] == pytest.approx(0.5)
    (ev,) = tel.tracer.to_dict()["traceEvents"]
    assert ev["name"] == "gap" and ev["cat"] == "serve"
    assert ev["ts"] == pytest.approx(0.25e6)
    assert ev["dur"] == pytest.approx(0.5e6)
    assert ev["args"] == {"rid": "r1"}


def test_span_annotates_the_profiler_only_with_a_tracer(monkeypatch):
    import jax.profiler
    entered = []

    class Spy:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    with obs.span("off"):
        pass
    with obs.activate(obs.MetricsRegistry()):
        with obs.span("stats_only"):
            pass
    with obs.Telemetry(trace=True).activate():
        with obs.span("outer"):
            with obs.span("inner"):
                pass
    assert entered == ["outer", "inner", "/inner", "/outer"]


def test_finalize_parts_lie_inside_finalize(world):
    idx, reads = world
    tele = obs.Telemetry(trace=True)
    res = Aligner.from_index(idx, AlignOptions(engine="batched"),
                             telemetry=tele).align(reads)
    st = res.stats
    assert st["time_finalize.cigar_s"] > 0 and st["time_finalize.replay_s"] > 0
    assert (st["time_finalize.cigar_s"] + st["time_finalize.replay_s"]
            <= st["time_finalize_s"])
    n_records = sum(len(a) for a in res.alignments)
    assert st["finalize_alignments"] == n_records > 0
    assert st["finalize_cigar_cells"] >= sum(
        a.qe - a.qb for alns in res.alignments for a in alns)
    evs = tele.tracer.to_dict()["traceEvents"]
    (fin,) = [e for e in evs if e["name"] == "finalize"]
    parts = [e for e in evs if e["name"].startswith("finalize.")]
    assert {e["name"] for e in parts} == {"finalize.replay", "finalize.cigar"}
    for e in parts:
        assert fin["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= fin["ts"] + fin["dur"] + 1e-3
    # the baseline driver's CIGARs are timed and counted too
    base = Aligner.from_index(idx, AlignOptions(engine="baseline"),
                              telemetry=True).align(reads)
    assert base.stats["finalize_cigar_cells"] == st["finalize_cigar_cells"]
    assert base.stats["time_finalize.cigar_s"] > 0


def test_finalize_records_nothing_with_telemetry_off(world):
    idx, reads = world
    stats = Aligner.from_index(idx, AlignOptions(engine="batched")).align(
        reads).stats
    assert not [k for k in stats
                if k.startswith(("finalize_", "time_", "compile"))]


class _NpSpy:
    """numpy, with every array ``np.full`` makes kept."""

    def __init__(self):
        self.full_arrays = []

    def __getattr__(self, name):
        return getattr(np, name)

    def full(self, *a, **kw):
        arr = np.full(*a, **kw)
        self.full_arrays.append(arr)
        return arr


@pytest.mark.parametrize("n, m, w", [
    (40, 40, 5),          # equal lengths, narrow band
    (40, 40, 100),        # equal lengths, band wider than the matrix
    (37, 52, 20),         # n < m
    (60, 41, 30),         # n > m
    (30, 45, 4),          # band widened to abs(n - m) + 3
    (1, 9, 0),            # one row
])
def test_cigar_cell_count_matches_the_dp(monkeypatch, n, m, w):
    """``band_cells`` against the cells the cell-at-a-time DP fills (the
    oracle of ``test_sam_cigar.py``, which the shipped DP matches)."""
    import test_sam_cigar as oracle
    from repro.core import sam
    from repro.core.bsw import BSWParams
    rng = np.random.default_rng(n * 1000 + m)
    q = rng.integers(0, 5, n).astype(np.uint8)
    t = rng.integers(0, 5, m).astype(np.uint8)
    spy = _NpSpy()
    monkeypatch.setattr(oracle, "np", spy)
    oracle.scalar_global_align_cigar(q, t, w, BSWParams())
    H = spy.full_arrays[0]                 # H, then E and F
    # a filled cell never holds the fill value: no score in the matrix is 0
    filled = int((H[1:, 1:] != -(1 << 28)).sum())
    assert sam.band_cells(n, m, w) == filled
    assert sam.band_cells(0, m, w) == sam.band_cells(n, 0, w) == 0


def test_compile_span_only_under_telemetry():
    import jax
    tele = obs.Telemetry(trace=True)       # registers the listener
    x = np.arange(7.0)
    jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()   # off
    assert len(tele.tracer) == 0
    with tele.activate() as reg:
        jax.jit(lambda v: v * 5.0 - 2.0)(x).block_until_ready()
    snap = reg.snapshot()
    assert snap["compiles"] == 1 and snap["time_compile_s"] > 0
    (ev,) = tele.tracer.to_dict()["traceEvents"]
    assert ev["name"] == "compile" and ev["cat"] == "compile"
    assert ev["args"]["fun"] == "jit(<lambda>)"
    assert ev["dur"] == pytest.approx(snap["time_compile_s"] * 1e6)


def test_fmocc_span_per_device_loop_carries_the_rounds_tasks(world,
                                                             monkeypatch):
    """One ``kernel.fmocc`` span per SMEM device loop; its ``tasks`` is the
    sum, over the rounds the host's NumPy path runs on the same input, of
    the call's task count: what per-round dispatch spans added up to."""
    from repro.core import smem as sm
    from repro.core.fmindex import occ_opt_np
    from repro.kernels.fmocc.ops import make_occ_fn

    idx, reads = world
    lens = np.full(len(reads), reads.shape[1], np.int64)
    opt = sm.MemOptions()
    host_tasks = []
    ext_round = sm._ext_round

    def counted(idx_, which, k, *a, **kw):
        host_tasks.append(len(k))
        return ext_round(idx_, which, k, *a, **kw)

    monkeypatch.setattr(sm, "_ext_round", counted)
    want = sm.collect_smems_batch(idx, reads, lens, opt, occ_fn=occ_opt_np)
    monkeypatch.setattr(sm, "_ext_round", ext_round)

    tele, reg = obs.Telemetry(trace=True), obs.MetricsRegistry()
    with tele.activate(reg):
        got = sm.collect_smems_batch(
            idx, reads, lens, opt,
            occ_fn=make_occ_fn("eta32", 256, interpret=True))
    assert got == want
    snap = reg.snapshot()
    spans = [e for e in tele.tracer.to_dict()["traceEvents"]
             if e["name"] == "kernel.fmocc"]
    assert len(spans) == snap["kernel_fmocc_dispatches"] \
        == snap["smem_occ_dispatches"] < len(host_tasks)
    assert sum(e["args"]["tasks"] for e in spans) == sum(host_tasks) > 0
    assert snap["smem_rounds"] == len(host_tasks)
    assert all(e["cat"] == "kernel" for e in spans)
