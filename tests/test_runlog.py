"""Run-scoped observability (PR 8): structured run log, live metrics
export, and cross-shard report aggregation.

Covers the contract on top of PR 6's telemetry:

* RunLog — JSONL envelope schema round-trip via ``read_runlog``
  (version / single run id / strictly-increasing seq enforced), no-op
  emits after close, structured warning capture that leaves the filter
  machinery (and the previous showwarning) intact;
* stream_sam wiring — stream_start/batch/stream_end events with
  computed rates, SAM byte-identity with the run log enabled vs
  disabled, and the crash diagnostic bundle (exception + partial
  Snapshot + last-batch context + trace tail) on an injected failure;
* shard merge identity — a 2-shard ``align_shard`` run merged via
  ``merge_profiles`` reproduces the unsharded run's shard-invariant
  counters exactly and the same SAM record set;
* LiveExporter — every observation of the atomically-rewritten files
  parses, under a concurrent writer; Prometheus exposition rendering;
* report CLI — multiple paths + globs, ``--merge -o`` re-loadable
  output, single-file rendering unchanged;
* straggler surfacing — ``min_samples`` knob + the per-shard wall
  table flags; and the regression gate's skip notes.
"""

import json
import pathlib
import sys
import threading
import time
import warnings

import pytest

from repro import obs
from repro.api import Aligner, AlignOptions
from repro.cli import main as cli_main
from repro.core import fmindex as fmx
from repro.data import make_reference, simulate_reads
from repro.ft import StragglerMonitor
from repro.io.fastq import FastqRecord, write_fastq
from repro.io.stream import open_batches
from repro.obs.metrics import Gauge, Hist, MultiValue, Snapshot

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    ref = make_reference(20000, seed=7)
    idx = fmx.build_index(ref)
    reads, _ = simulate_reads(ref, 14, 101, seed=3)
    fq = tmp_path_factory.mktemp("runlog") / "reads.fq"
    write_fastq(fq, [FastqRecord(f"read{i}",
                                 "".join("ACGTN"[b] for b in row), None)
                     for i, row in enumerate(reads)])
    return idx, reads, str(fq)


# ---------------------------------------------------------------------
# RunLog core: envelope schema, validation, lifecycle
# ---------------------------------------------------------------------

def test_runlog_roundtrip_and_envelope(tmp_path):
    p = tmp_path / "run.jsonl"
    with obs.RunLog(p) as rl:
        rl.manifest("test-tool", argv=["--x", "1"], engine="batched",
                    options=AlignOptions(), extra="hi")
        rl.batch(0, reads=8, records=9, batch_s=0.25, reads_total=8,
                 records_total=9, elapsed_s=0.5, total_reads=16)
        rl.end(status="ok", n_reads=8)
    events = obs.read_runlog(p)
    assert [e["event"] for e in events] == ["run_start", "batch", "run_end"]
    run_ids = {e["run"] for e in events}
    assert len(run_ids) == 1 and events[0]["run"] == rl.run_id
    assert [e["seq"] for e in events] == [0, 1, 2]
    for e in events:
        assert e["v"] == obs.RUNLOG_VERSION
        assert isinstance(e["t"], float) and isinstance(e["ts"], float)
    man = events[0]
    assert man["tool"] == "test-tool" and man["argv"] == ["--x", "1"]
    assert man["options"]["engine"] == "batched" and man["extra"] == "hi"
    b = events[1]
    assert b["reads_per_s"] == pytest.approx(8 / 0.5)
    assert b["eta_s"] == pytest.approx(8 / 16.0)
    assert events[2]["status"] == "ok"


@pytest.mark.parametrize("engine", ["batched", "baseline"])
def test_cli_mem_manifest_records_device(world, tmp_path, engine):
    """``repro.cli mem`` says where it ran; engines that dispatch no
    Pallas kernel record no kernel mode."""
    import jax
    from repro.data.reads import write_fasta
    idx, _, fq = world
    fa = tmp_path / "ref.fa"
    write_fasta(fa, [("chr1", make_reference(20000, seed=7))])
    rl = tmp_path / "run.jsonl"
    assert cli_main(["mem", str(fa), fq, "--engine", engine, "--no-pg",
                     "-o", str(tmp_path / "out.sam"),
                     "--runlog", str(rl)]) == 0
    man = obs.read_runlog(rl)[0]
    assert man["event"] == "run_start"
    assert man["device"] == {"platform": jax.devices()[0].platform,
                             "kind": jax.devices()[0].device_kind,
                             "count": len(jax.devices())}


def test_runlog_rejects_malformed_files(tmp_path):
    good = {"v": obs.RUNLOG_VERSION, "run": "r1", "seq": 0, "t": 0.0,
            "ts": 0.0, "event": "run_start"}

    def write(name, lines):
        p = tmp_path / name
        p.write_text("\n".join(lines) + "\n")
        return p

    with pytest.raises(ValueError, match=r"\.jsonl:2: bad JSONL"):
        obs.read_runlog(write("garbage.jsonl",
                              [json.dumps(good), "{not json"]))
    with pytest.raises(ValueError, match="missing 'seq'"):
        obs.read_runlog(write("noseq.jsonl", [json.dumps(
            {k: v for k, v in good.items() if k != "seq"})]))
    with pytest.raises(ValueError, match="version"):
        obs.read_runlog(write("badv.jsonl",
                              [json.dumps(dict(good, v=99))]))
    with pytest.raises(ValueError, match="mixed run ids"):
        obs.read_runlog(write("mixed.jsonl", [
            json.dumps(good), json.dumps(dict(good, run="r2", seq=1))]))
    with pytest.raises(ValueError, match="seq not increasing"):
        obs.read_runlog(write("dupseq.jsonl", [
            json.dumps(good), json.dumps(dict(good, event="x"))]))


def test_runlog_emit_after_close_is_noop(tmp_path):
    rl = obs.RunLog(tmp_path / "r.jsonl")
    assert rl.emit("run_start") is not None
    rl.close()
    assert rl.closed and rl.emit("run_end") is None
    assert len(obs.read_runlog(rl.path)) == 1


def test_run_ids_unique_and_index_fingerprint(world):
    from repro.core.contig import build_contig_index
    idx, _, _ = world
    assert obs.new_run_id() != obs.new_run_id()
    # a bare FMIndex has no contig table: length only
    assert obs.index_fingerprint(idx) == {"N": int(idx.N)}
    cidx = build_contig_index({"chr1": make_reference(500, seed=1),
                               "chr2": make_reference(300, seed=2)})
    fp = obs.index_fingerprint(cidx)
    assert fp["N"] == int(cidx.N) and fp["n_contigs"] == 2
    assert len(fp["contigs_sha1"]) == 12
    assert fp["contigs"] == ["chr1", "chr2"]     # small: listed inline
    assert fp == obs.index_fingerprint(cidx)     # deterministic
    other = build_contig_index({"chr1": make_reference(501, seed=1)})
    assert obs.index_fingerprint(other)["contigs_sha1"] != fp["contigs_sha1"]


def test_capture_warnings_structured_and_forwarded(tmp_path):
    seen = []
    with obs.RunLog(tmp_path / "w.jsonl") as rl:
        with warnings.catch_warnings():
            warnings.simplefilter("always")
            warnings.showwarning = (
                lambda m, c, f, ln, *a: seen.append(str(m)))
            with rl.capture_warnings():
                warnings.warn("interpret forced", RuntimeWarning)
    evs = [e for e in obs.read_runlog(rl.path) if e["event"] == "warning"]
    assert len(evs) == 1
    assert evs[0]["message"] == "interpret forced"
    assert evs[0]["category"] == "RuntimeWarning"
    assert ":" in evs[0]["where"]
    assert seen == ["interpret forced"]          # previous handler kept
    # filters untouched: an error-configured warning still raises
    with obs.RunLog(tmp_path / "e.jsonl") as rl2:
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            with rl2.capture_warnings():
                with pytest.raises(RuntimeWarning):
                    warnings.warn("boom", RuntimeWarning)


# ---------------------------------------------------------------------
# stream_sam wiring: events, byte-identity, crash bundle
# ---------------------------------------------------------------------

def test_stream_sam_runlog_events_and_sam_identity(tmp_path, world):
    idx, reads, fq = world
    al = Aligner.from_index(idx, telemetry=True)
    out_log = tmp_path / "log.sam"
    rl = obs.RunLog(tmp_path / "run.jsonl")
    summary = al.stream_sam(open_batches(fq, batch_size=8), str(out_log),
                            runlog=rl, total_reads=len(reads))
    rl.close()
    out_plain = tmp_path / "plain.sam"
    al.stream_sam(open_batches(fq, batch_size=8), str(out_plain))
    assert out_log.read_text() == out_plain.read_text()
    events = obs.read_runlog(rl.path)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "stream_start" and kinds[-1] == "stream_end"
    batches = [e for e in events if e["event"] == "batch"]
    assert len(batches) == summary["n_batches"] == 2
    assert batches[-1]["reads_total"] == len(reads)
    assert batches[-1]["reads_per_s"] > 0
    assert batches[0]["eta_s"] is not None       # total_reads was given
    end = events[-1]
    assert end["n_reads"] == len(reads) and end["reads_per_s"] > 0


def test_stream_sam_crash_bundle(tmp_path, world):
    idx, _, fq = world
    al = Aligner.from_index(idx, telemetry=obs.Telemetry(trace=True))

    def dying_batches():
        it = iter(open_batches(fq, batch_size=8))
        yield next(it)
        raise RuntimeError("disk on fire")

    rl = obs.RunLog(tmp_path / "crash.jsonl")
    with pytest.raises(RuntimeError, match="disk on fire"):
        al.stream_sam(dying_batches(), str(tmp_path / "x.sam"), runlog=rl)
    rl.end(status="error")
    rl.close()
    events = obs.read_runlog(rl.path)
    crashes = [e for e in events if e["event"] == "crash"]
    assert len(crashes) == 1
    c = crashes[0]
    assert c["exc_type"] == "RuntimeError" and "disk on fire" in c["message"]
    assert "dying_batches" in c["traceback"]
    # the bundle carries the PARTIAL run state: one batch completed
    snap = Snapshot.from_jsonable(c["snapshot"])
    assert snap["sa_lookups"] > 0
    assert c["batch"]["i"] == 0 and c["batch"]["size"] == 8
    assert c["batch"]["first_name"].startswith("read")
    assert c["trace_tail"] and all("name" in e for e in c["trace_tail"])
    assert events[-1]["event"] == "run_end"
    assert events[-1]["status"] == "error"


# ---------------------------------------------------------------------
# cross-shard merge: counter identity + straggler table
# ---------------------------------------------------------------------

def test_shard_merge_counter_identity(tmp_path, world):
    from repro.dist.api import align_shard
    idx, reads, fq = world
    al = Aligner.from_index(idx, telemetry=True)
    full = al.stream_sam(open_batches(fq, batch_size=8),
                         str(tmp_path / "full.sam"))
    rl = obs.RunLog(tmp_path / "shards.jsonl")
    parts = []
    for i in range(2):
        s = align_shard(al, fq, out=str(tmp_path / f"s{i}.sam"),
                        spec=f"{i}/2", batch_size=8, runlog=rl)
        obs.write_profile(tmp_path / f"s{i}.json", s["stats"],
                          wall_s=s["wall_s"],
                          meta={"shard": f"{i}/2", "reads": s["n_reads"],
                                "engine": "batched"})
        parts.append(s)
    rl.close()
    paths = [str(tmp_path / "s0.json"), str(tmp_path / "s1.json")]
    merged = obs.merge_profiles([obs.read_profile(p) for p in paths],
                                paths=paths)
    # the tested guarantee: merged sharded counters == unsharded run
    for key in obs.SHARD_INVARIANT_COUNTERS:
        assert merged["snapshot"][key] == full["stats"][key], key
    assert merged["snapshot"]["io_reads"] == len(reads)
    # same alignments, just partitioned: SAM record sets match
    full_body = sorted(ln for ln in
                       (tmp_path / "full.sam").read_text().splitlines()
                       if not ln.startswith("@"))
    shard_body = sorted(
        ln for i in range(2)
        for ln in (tmp_path / f"s{i}.sam").read_text().splitlines()
        if not ln.startswith("@"))
    assert shard_body == full_body
    # merged bookkeeping: wall is the max, sum kept alongside
    walls = [p["wall_s"] for p in parts]
    assert merged["wall_s"] == max(walls)
    assert merged["meta"]["wall_sum_s"] == pytest.approx(sum(walls), rel=1e-6)
    assert [s["shard"] for s in merged["shards"]] == ["0/2", "1/2"]
    # the run log bracketed each shard
    kinds = [e["event"] for e in obs.read_runlog(rl.path)]
    assert kinds.count("shard_start") == 2 and kinds.count("shard_end") == 2


def test_straggler_min_samples_and_wall_table():
    # default warm-up suppresses early judgments ...
    mon = StragglerMonitor(window=32, threshold=1.5)
    assert mon.min_samples == 8
    assert mon.observe(0, host=0, step_time=10.0) is None
    # ... small-N callers lower it
    mon2 = StragglerMonitor(window=8, threshold=1.5, min_samples=2)
    assert mon2.observe(0, host=0, step_time=0.1) is None
    ev = mon2.observe(1, host=1, step_time=0.1)
    assert ev is None                        # at the median: not straggling
    ev = mon2.observe(2, host=2, step_time=1.0)
    assert ev is not None and ev.action == "rebalance"
    table = obs.shard_wall_table([
        {"shard": "0/3", "wall_s": 1.0, "reads": 100},
        {"shard": "1/3", "wall_s": 1.1, "reads": 100},
        {"shard": "2/3", "wall_s": 9.0, "reads": 100},
    ])
    lines = table.splitlines()
    assert "STRAGGLER" in table
    flagged = [ln for ln in lines if "STRAGGLER" in ln]
    assert len(flagged) == 1 and "2/3" in flagged[0]
    assert "median 1.100s over 3 shard(s)" in table
    empty = obs.shard_wall_table([{"shard": "0/1", "wall_s": None}])
    assert "no shard wall times" in empty


# ---------------------------------------------------------------------
# live export: atomicity under concurrency + Prometheus rendering
# ---------------------------------------------------------------------

def test_live_exporter_atomic_under_concurrent_writes(tmp_path):
    lock = threading.Lock()
    state = {"n": 0}
    reg = obs.MetricsRegistry()

    def source():
        with lock:
            snap = reg.snapshot()
            snap["writer_n"] = state["n"]
        return snap

    stop = threading.Event()

    def writer():
        with obs.activate(reg):
            while not stop.is_set():
                with lock:
                    with obs.span("bsw"):
                        obs.count("bsw_tasks", 3)
                        obs.observe("lanes", 64)
                    state["n"] += 1

    exp = obs.LiveExporter(tmp_path / "live", interval=0.002,
                           meta={"run": "test-run", "shard": "0/1"})
    t = threading.Thread(target=writer)
    t.start()
    try:
        exp.start(source)
        with pytest.raises(RuntimeError, match="already started"):
            exp.start(source)
        deadline = time.time() + 0.3
        parses = 0
        while time.time() < deadline:
            # atomicity: every observation of the file parses
            with open(exp.json_path) as f:
                payload = json.load(f)
            assert payload["version"] == obs.EXPORT_VERSION
            assert payload["meta"]["run"] == "test-run"
            parses += 1
    finally:
        stop.set()
        t.join()
        exp.stop()
    exp.stop()                                # idempotent
    assert parses > 0 and exp.n_flushes >= 2 and exp.last_error is None
    final = json.loads(open(exp.json_path).read())
    snap = Snapshot.from_jsonable(final["snapshot"])
    # final flush reflects the complete run state
    assert snap["writer_n"] == state["n"] > 0
    assert snap["bsw_tasks"] == 3 * state["n"]
    prom = open(exp.prom_path).read()
    assert "# TYPE repro_bsw_tasks counter" in prom
    assert 'repro_run_info{run="test-run",shard="0/1"} 1' in prom


def test_prometheus_text_rendering():
    h = Hist.new((1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    snap = Snapshot(sa_lookups=42, n_length_groups=Gauge(3.0), lanes=h,
                    pe_ok=True, note="skip me",
                    mv=MultiValue([1, 2]))
    snap["time_kernel.bsw_s"] = 0.5          # name needs sanitizing
    text = obs.prometheus_text(snap, {"engine": "batched"}, ts=123.0)
    assert 'repro_run_info{engine="batched"} 1' in text
    assert "# TYPE repro_sa_lookups counter\nrepro_sa_lookups 42" in text
    assert "# TYPE repro_n_length_groups gauge" in text
    assert "# TYPE repro_lanes histogram" in text
    assert 'repro_lanes_bucket{le="1"} 1' in text
    assert 'repro_lanes_bucket{le="10"} 2' in text
    assert 'repro_lanes_bucket{le="+Inf"} 3' in text
    assert "repro_lanes_sum 55.5" in text and "repro_lanes_count 3" in text
    assert "repro_time_kernel_bsw_s 0.5" in text
    assert "pe_ok" not in text and "note" not in text and "mv" not in text
    assert "repro_export_timestamp_seconds 123.000" in text


# ---------------------------------------------------------------------
# report CLI: globs, --merge, single-file path unchanged
# ---------------------------------------------------------------------

def _fake_profile(path, *, shard, wall, reads):
    snap = Snapshot(io_reads=reads, sa_lookups=10 * reads,
                    time_bsw_s=wall / 2)
    obs.write_profile(path, snap, wall_s=wall,
                      meta={"shard": shard, "reads": reads,
                            "engine": "batched"})


def test_report_cli_merge_and_globs(tmp_path, capsys):
    for i, wall in enumerate((1.0, 4.0)):
        _fake_profile(tmp_path / f"shard{i}.json", shard=f"{i}/2",
                      wall=wall, reads=50)
    merged_path = tmp_path / "merged.json"
    rc = cli_main(["report", "--merge", str(tmp_path / "shard*.json"),
                   "-o", str(merged_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "per-shard wall time" in out and "STRAGGLER" in out
    payload = obs.read_profile(merged_path)   # merged artifact re-loads
    assert payload["snapshot"]["io_reads"] == 100
    assert payload["wall_s"] == 4.0
    assert payload["meta"]["merged_from"] == 2
    # duplicate expansion (glob + explicit path) dedupes
    rc = cli_main(["report", str(tmp_path / "shard*.json"),
                   str(tmp_path / "shard0.json")])
    out = capsys.readouterr().out
    assert rc == 0 and "2 shard(s)" in out


def test_report_cli_single_file_unchanged(tmp_path, capsys):
    _fake_profile(tmp_path / "one.json", shard="0/1", wall=2.0, reads=25)
    rc = cli_main(["report", str(tmp_path / "one.json")])
    assert rc == 0
    payload = obs.read_profile(tmp_path / "one.json")
    expected = obs.render(payload["snapshot"], wall_s=payload["wall_s"],
                          meta=payload["meta"])
    assert capsys.readouterr().out == expected + "\n"
    rc = cli_main(["report", str(tmp_path / "missing.json")])
    assert rc == 2


# ---------------------------------------------------------------------
# regression gate: everything skipped is surfaced
# ---------------------------------------------------------------------

def test_regression_gate_notes_every_skip():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from benchmarks.regression import compare, render
    payload = {
        "ci_mode": True, "python": "3.12.1", "platform": "linux-B",
        "suites_s": {"smem": 2.0},
        "rows": [{"name": "smem.tasks", "value": 100, "derived": ""},
                 {"name": "smem.wall_s", "value": 1.5, "derived": ""}],
        "kernel_breakdown": {
            "stages": [{"stage": "smem", "time_s": 0.5}],
            "kernels": {"kernel.fmocc": 0.25}, "counters": {"sa": 7}},
    }
    base = dict(payload, python="3.11.0", platform="linux-A",
                suites_s={"smem": 9.0},
                rows=[{"name": "smem.tasks", "value": 100, "derived": ""},
                      {"name": "smem.wall_s", "value": 9.9, "derived": ""}])
    failures, notes = compare(payload, base)
    assert failures == []
    text = "\n".join(notes)
    for field in ("python", "platform", "suites_s"):
        assert f"field {field}: machine-varying" in text
    assert "smem.wall_s: timing row, not compared" in text
    assert "stage timing(s) checked for activity only" in text
    assert "kernel span 'kernel.fmocc' timing not compared" in text
    assert ("summary: 1 row(s) compared, 1 timing row(s) and "
            "3 machine-varying field(s) excluded") in text
    assert "PASS" in render(failures, notes)
