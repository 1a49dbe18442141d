"""Tests for the always-on alignment service (``repro.serve``).

The load-bearing assertion throughout: SAM records streamed back for one
request are byte-identical to an offline ``Aligner.stream_sam`` over the
same reads and options — under concurrent clients, arbitrary coalescing
(forced deterministically via ``pause()``/``resume()``), SE and PE, and
multi-contig references.  Plus the lifecycle edges: zero-read requests,
oversized reads, backpressure, client disconnects mid-batch, deadline
expiry without poisoning the cohort, and drain-on-shutdown.  The
``Aligner`` thread-safety regression (N threads hammering one facade)
lives here too — it is the property the server's shared-aligner cache
stands on.
"""

from __future__ import annotations

import io
import threading
import time

import pytest

from repro import obs
from repro.api import Aligner
from repro.core import fmindex as fmx
from repro.core.contig import build_contig_index
from repro.data import (decode, make_reference, simulate_pairs,
                        simulate_pairs_multi, simulate_reads,
                        simulate_reads_multi, simulate_reference)
from repro.io.stream import _pack_pe, _pack_se
from repro.options import AlignOptions
from repro.serve import (AlignmentServer, Overloaded, RequestQueue,
                         ServeClient, ServeError, protocol)
from repro.serve.batcher import Request


# ---------------------------------------------------------------------
# Worlds
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def world():
    ref = make_reference(30000, seed=5)
    idx = fmx.build_index(ref)
    reads, _ = simulate_reads(ref, 12, 101, seed=3)
    r1, r2, _ = simulate_pairs(ref, 10, 101, insert_mean=300, insert_std=30,
                               seed=9, burst_frac=0.2)
    se = [(f"read{i}", decode(r)) for i, r in enumerate(reads)]
    pe = [(f"pair{i}", decode(a), decode(b))
          for i, (a, b) in enumerate(zip(r1, r2))]
    return idx, se, pe


@pytest.fixture(scope="module")
def contig_world():
    contigs = simulate_reference(45000, 3, seed=11)
    idx = build_contig_index(contigs)
    r1, r2, _ = simulate_pairs_multi(contigs, 8, 101, seed=13,
                                     insert_mean=300, insert_std=30,
                                     burst_frac=0.1)
    reads, _ = simulate_reads_multi(contigs, 8, 101, seed=29)
    se = [(f"mread{i}", decode(r)) for i, r in enumerate(reads)]
    pe = [(f"mpair{i}", decode(a), decode(b))
          for i, (a, b) in enumerate(zip(r1, r2))]
    return idx, se, pe


@pytest.fixture()
def server(world):
    idx, _, _ = world
    srv = AlignmentServer(idx)
    srv.start()
    yield srv
    srv.shutdown()


def offline_se(idx, items, options=None, header=False, **aligner_kw):
    """The conformance reference: one offline stream_sam run."""
    al = Aligner(idx, options, **aligner_kw)
    buf = io.StringIO()
    al.stream_sam([_pack_se([n for n, _ in items],
                            [s for _, s in items])],
                  buf, header=header)
    return buf.getvalue().splitlines()


def offline_pe(idx, items, options=None, header=False, **aligner_kw):
    al = Aligner(idx, options, **aligner_kw)
    buf = io.StringIO()
    al.stream_sam([_pack_pe([n for n, _, _ in items],
                            [a for _, a, _ in items],
                            [b for _, _, b in items])],
                  buf, header=header)
    return buf.getvalue().splitlines()


def _wait_queued(srv, n, timeout=5.0):
    """Wait until ``n`` requests reached a PAUSED server's scheduler:
    the scheduler pops the first arrival before blocking on the pause
    gate, so at most one request is held outside the queue."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        accepted = srv.metrics.snapshot().get("serve_requests", 0)
        if accepted >= n and len(srv.queue) >= n - 1:
            time.sleep(0.1)               # let in-flight puts settle
            return
        time.sleep(0.01)
    raise AssertionError(
        f"only {srv.metrics.snapshot().get('serve_requests', 0)}/{n} "
        f"requests accepted ({len(srv.queue)} queued) after {timeout}s")


# ---------------------------------------------------------------------
# Conformance: byte-identity with the offline run
# ---------------------------------------------------------------------

def test_se_identity_with_header(server, world):
    idx, se, _ = world
    res = ServeClient.connect(*server.address).align(se, header=True)
    assert res.header + res.sam == offline_se(idx, se, header=True)
    assert res.n_records == len(res.sam)


def test_pe_identity(server, world):
    idx, _, pe = world
    res = ServeClient.connect(*server.address).align_pairs(pe)
    assert res.sam == offline_pe(idx, pe)
    assert len(res.sam) == 2 * len(pe)        # emit_pair: 2 lines/pair


def test_per_request_options_and_rg(server, world):
    """Per-request flags land in their own cohort; @RG is request-scoped."""
    idx, se, _ = world
    flags = {"-T": 25, "-R": "@RG\\tID:svc"}
    res = ServeClient.connect(*server.address).align(
        se, flags=flags, header=True)
    want = offline_se(idx, se, AlignOptions.from_flags(
        {"-T": 25, "-R": "@RG\\tID:svc"}), header=True)
    assert res.header + res.sam == want
    assert any(ln.startswith("@RG") for ln in res.header)
    assert all("RG:Z:svc" in ln for ln in res.sam)


def test_se_coalescing_identity(server, world):
    """Force 3 requests into ONE engine batch; each response must equal
    its own offline run (split correctness + composition independence)."""
    idx, se, _ = world
    parts = [se[:5], se[5:8], se[8:]]
    server.pause()
    results = [None] * len(parts)

    def worker(i):
        with ServeClient.connect(*server.address) as c:
            results[i] = c.align(parts[i])

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(parts))]
    for t in threads:
        t.start()
    _wait_queued(server, len(parts))
    before = server.metrics.snapshot().get("serve_batches", 0)
    server.resume()
    for t in threads:
        t.join(timeout=30)
    for part, res in zip(parts, results):
        assert res.sam == offline_se(idx, part)
    after = server.live_stats()
    assert after.get("serve_batches", 0) - before == 1   # ONE batch ran


def test_pe_coalescing_with_frozen_stats(world):
    """PE requests coalesce only with frozen insert-size stats; output
    stays identical to per-request offline runs with the same stats."""
    idx, _, pe = world
    stats = Aligner(idx).estimate_pe_stats(
        _pack_pe([n for n, _, _ in pe], [a for _, a, _ in pe],
                 [b for _, _, b in pe]))
    srv = AlignmentServer(idx, pe_stats=stats)
    srv.start()
    try:
        parts = [pe[:4], pe[4:7], pe[7:]]
        srv.pause()
        results = [None] * len(parts)

        def worker(i):
            with ServeClient.connect(*srv.address) as c:
                results[i] = c.align_pairs(parts[i])

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(parts))]
        for t in threads:
            t.start()
        _wait_queued(srv, len(parts))
        before = srv.metrics.snapshot().get("serve_batches", 0)
        srv.resume()
        for t in threads:
            t.join(timeout=30)
        for part, res in zip(parts, results):
            assert res.sam == offline_pe(idx, part, pe_stats=stats)
        assert srv.live_stats().get("serve_batches", 0) - before == 1
    finally:
        srv.shutdown()


def test_multi_contig_identity(contig_world):
    idx, se, pe = contig_world
    srv = AlignmentServer(idx)
    srv.start()
    try:
        with ServeClient.connect(*srv.address) as c:
            assert c.align(se, header=True).sam == offline_se(idx, se)
            assert c.align_pairs(pe).sam == offline_pe(idx, pe)
            hdr = c.align(se, header=True).header
            assert sum(ln.startswith("@SQ") for ln in hdr) == 3
    finally:
        srv.shutdown()


def test_concurrent_clients_identity(server, world):
    """8 clients hammering SE+PE concurrently, every response offline-
    identical — the acceptance-criteria scenario."""
    idx, se, pe = world
    errors: list = []

    def worker(i):
        try:
            with ServeClient.connect(*server.address) as c:
                for _ in range(3):
                    if i % 2:
                        sub = se[i % len(se):] or se
                        assert c.align(sub).sam == offline_se(idx, sub)
                    else:
                        assert c.align_pairs(pe).sam == offline_pe(idx, pe)
        except Exception as e:              # noqa: BLE001 — collected
            errors.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errors, errors


# ---------------------------------------------------------------------
# Edge cases
# ---------------------------------------------------------------------

def test_zero_read_request(server, world):
    with ServeClient.connect(*server.address) as c:
        res = c.align([], header=True)
        assert res.sam == [] and res.n_records == 0
        assert any(ln.startswith("@SQ") for ln in res.header)
        assert c.align_pairs([]).n_records == 0


def test_oversized_read_rejected(world):
    idx, se, _ = world
    srv = AlignmentServer(idx, max_read_len=150)
    srv.start()
    try:
        with ServeClient.connect(*srv.address) as c:
            with pytest.raises(ServeError) as ei:
                c.align([("big", "A" * 151)])
            assert ei.value.code == protocol.ERR_READ_TOO_LONG
            with pytest.raises(ServeError) as ei:
                c.align_pairs([("p", "ACGT", "A" * 400)])
            assert ei.value.code == protocol.ERR_READ_TOO_LONG
            # the connection survives a rejected request
            assert c.align(se[:2]).sam == offline_se(idx, se[:2])
    finally:
        srv.shutdown()


def test_bad_requests_are_structured(server):
    with ServeClient.connect(*server.address) as c:
        for req in ({"op": "align"},                      # no reads
                    {"op": "align", "reads": [["x"]]},    # arity
                    {"op": "align", "reads": [["x", ""]]},  # empty seq
                    {"op": "align", "reads": [["x", "ACGT"]],
                     "flags": {"-Z": 1}},                 # unknown flag
                    {"op": "nope"}):
            protocol.send_frame(c._sock, req)
            frame = protocol.recv_frame(c._sock)
            assert frame["type"] == "error"
            assert frame["code"] == protocol.ERR_BAD_REQUEST


def test_backpressure_overloaded(world):
    idx, se, _ = world
    srv = AlignmentServer(idx, max_queue=2)
    srv.start()
    try:
        srv.pause()
        clients, ok, rejected = [], [], []
        for i in range(6):
            c = ServeClient.connect(*srv.address)
            clients.append(c)
            protocol.send_frame(c._sock, {"op": "align", "id": f"q{i}",
                                          "reads": [["r", se[0][1]]]})
        deadline = time.time() + 5
        while (srv.metrics.snapshot().get("serve_requests", 0) < 6 and
               time.time() < deadline):
            time.sleep(0.01)
        srv.resume()
        for c in clients:
            try:
                frames = []
                while True:
                    f = protocol.recv_frame(c._sock)
                    frames.append(f)
                    if f["type"] in ("end", "error"):
                        break
                (rejected if frames[-1]["type"] == "error" else ok).append(
                    frames[-1])
            finally:
                c.close()
        assert all(f["code"] == protocol.ERR_OVERLOADED for f in rejected)
        assert len(ok) >= 2 and len(rejected) >= 1
        assert len(ok) + len(rejected) == 6
    finally:
        srv.shutdown()


def test_client_disconnect_mid_batch(server, world):
    """A client that vanishes before its response is sent must not poison
    the coalesced batch: the surviving request still gets exact bytes."""
    idx, se, _ = world
    server.pause()
    ghost = ServeClient.connect(*server.address)
    protocol.send_frame(ghost._sock, {"op": "align", "id": "ghost",
                                      "reads": [["g", se[0][1]]]})
    _wait_queued(server, 1)
    result = {}

    def worker():
        with ServeClient.connect(*server.address) as c:
            result["sam"] = c.align(se[2:6]).sam

    t = threading.Thread(target=worker)
    t.start()
    _wait_queued(server, 2)
    ghost.close()                              # vanish before scheduling
    time.sleep(0.1)
    server.resume()
    t.join(timeout=30)
    assert result["sam"] == offline_se(idx, se[2:6])


def test_deadline_does_not_poison_cohort(server, world):
    """An expired request gets a structured deadline error; a same-cohort
    request in the SAME batch still succeeds with exact bytes."""
    idx, se, _ = world
    server.pause()
    outcome = {}

    def doomed():
        with ServeClient.connect(*server.address) as c:
            try:
                c.align(se[:3], deadline_s=0.05)
                outcome["doomed"] = "ok"
            except ServeError as e:
                outcome["doomed"] = e.code

    def survivor():
        with ServeClient.connect(*server.address) as c:
            outcome["sam"] = c.align(se[3:6]).sam

    t1 = threading.Thread(target=doomed)
    t2 = threading.Thread(target=survivor)
    t1.start()
    t2.start()
    _wait_queued(server, 2)
    time.sleep(0.2)                            # let the 0.05s deadline pass
    server.resume()
    t1.join(timeout=30)
    t2.join(timeout=30)
    assert outcome["doomed"] == protocol.ERR_DEADLINE
    assert outcome["sam"] == offline_se(idx, se[3:6])
    assert server.live_stats().get("serve_timeouts", 0) >= 1


def test_shutdown_drains_queue(world):
    idx, se, _ = world
    srv = AlignmentServer(idx)
    srv.start()
    srv.pause()
    results = [None] * 3

    def worker(i):
        with ServeClient.connect(*srv.address) as c:
            results[i] = c.align(se[i * 4:(i + 1) * 4])

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    _wait_queued(srv, 3)
    srv.shutdown(drain=True)                  # resumes + drains + stops
    for t in threads:
        t.join(timeout=30)
    for i in range(3):
        assert results[i].sam == offline_se(idx, se[i * 4:(i + 1) * 4])


def test_rejects_after_shutdown(world):
    idx, se, _ = world
    srv = AlignmentServer(idx)
    srv.start()
    c = ServeClient.connect(*srv.address)
    srv.shutdown()
    with pytest.raises((ServeError, ConnectionError, OSError)):
        res = c.align(se[:1])
        raise AssertionError(f"unexpected success: {res}")
    c.close()


# ---------------------------------------------------------------------
# Queue mechanics (no sockets)
# ---------------------------------------------------------------------

def _req(i, op="align", options=None, n=1):
    return Request(id=f"q{i}", op=op, names=[f"r{j}" for j in range(n)],
                   seqs=(["ACGT"] * n if op == "align"
                         else [("ACGT", "ACGT")] * n),
                   options=options or AlignOptions(), engine=None,
                   header=False, deadline=None, conn=None)


def test_queue_cohorts_and_budget():
    q = RequestQueue(maxsize=8)
    strict = AlignOptions(min_score=40)
    for i in range(3):
        q.put(_req(i, n=2))
    q.put(_req(3, options=strict, n=2))
    q.put(_req(4, op="align_pairs", n=1))
    first = q.get()
    key = first.cohort_key(False)
    taken = q.take_cohort(key, False, budget_reads=2)
    assert [r.id for r in taken] == ["q1"]     # budget stops at 2 reads
    taken = q.take_cohort(key, False, budget_reads=99)
    assert [r.id for r in taken] == ["q2"]     # q3/q4 are other cohorts
    assert len(q) == 2                         # order preserved for them
    assert q.get().id == "q3"
    # PE requests never share a cohort without frozen stats
    pe1, pe2 = _req(8, op="align_pairs"), _req(9, op="align_pairs")
    assert pe1.cohort_key(False) != pe2.cohort_key(False)
    assert pe1.cohort_key(True) == pe2.cohort_key(True)


def test_queue_overload_and_close():
    q = RequestQueue(maxsize=1)
    q.put(_req(0))
    with pytest.raises(Overloaded):
        q.put(_req(1))
    q.close()
    assert q.get().id == "q0"                  # drains after close
    from repro.serve import QueueClosed
    with pytest.raises(QueueClosed):
        q.get()


# ---------------------------------------------------------------------
# Observability wiring
# ---------------------------------------------------------------------

def test_runlog_and_live_export(tmp_path, world):
    idx, se, pe = world
    runlog = obs.RunLog(tmp_path / "serve.runlog.jsonl")
    runlog.manifest("test serve", engine="batched")
    exporter = obs.LiveExporter(str(tmp_path / "serve.live"), interval=0.05)
    srv = AlignmentServer(idx, runlog=runlog, exporter=exporter)
    srv.start()
    with ServeClient.connect(*srv.address) as c:
        c.align(se)
        c.align_pairs(pe)
    srv.shutdown()
    events = obs.read_runlog(tmp_path / "serve.runlog.jsonl")
    kinds = [e["event"] for e in events]
    assert "serve_start" in kinds and "serve_stop" in kinds
    assert kinds.count("request") == 2
    assert kinds.count("batch_coalesced") == 2
    assert kinds.count("request_done") == 2
    reqs = [e for e in events if e["event"] == "batch_coalesced"]
    assert {e["op"] for e in reqs} == {"align", "align_pairs"}
    prom = (tmp_path / "serve.live.prom").read_text()
    assert "serve_requests" in prom and "serve_batches" in prom
    for ln in prom.splitlines():               # textfile format parses
        assert not ln or ln.startswith("#") or len(ln.split()) >= 2


# ---------------------------------------------------------------------
# Satellite: Aligner thread-safety under concurrent calls
# ---------------------------------------------------------------------

def _merge_counters(snaps):
    total = obs.Snapshot.merge_all(snaps)
    # ``compiles`` counts the programs JAX built during the call: the
    # serial calls build them, the threaded ones find them built
    return {k: v for k, v in total.items()
            if isinstance(v, (int, float)) and not k.startswith("time")
            and k != "compiles"}


@pytest.mark.parametrize("engine", ["batched", "pallas"])
def test_aligner_thread_safety(world, engine, monkeypatch):
    """N threads hammering ONE Aligner: every per-call SAM identical to
    the serial run, and merged counters equal the serial merge (no lost
    updates in telemetry, no racing kernel-config attach)."""
    monkeypatch.setenv("REPRO_PALLAS_SWEEP", "0")
    idx, se, _ = world
    n = 4 if engine == "batched" else 2
    al = Aligner(idx, AlignOptions(engine=engine), telemetry=True)
    batches = [_pack_se([f"t{i}_{j}" for j in range(3)],
                        [s for _, s in se[i * 3:i * 3 + 3]])
               for i in range(n)]
    serial = [al.align(b) for b in batches]
    sams = [None] * n
    stats = [None] * n
    errors: list = []

    def worker(i):
        try:
            res = al.align(batches[i])
            sams[i] = res.sam()
            stats[i] = res.stats
        except Exception as e:              # noqa: BLE001 — collected
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    for i in range(n):
        assert sams[i] == serial[i].sam(), f"thread {i} bytes diverged"
    assert _merge_counters(stats) == \
        _merge_counters([r.stats for r in serial])


def test_aligner_pe_thread_safety(world):
    idx, _, pe = world
    al = Aligner(idx, telemetry=True)
    batch = _pack_pe([n for n, _, _ in pe], [a for _, a, _ in pe],
                     [b for _, _, b in pe])
    serial = al.align_pairs(batch)
    out = [None] * 3
    threads = [threading.Thread(
        target=lambda i=i: out.__setitem__(i, al.align_pairs(batch).sam()))
        for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert all(o == serial.sam() for o in out)


# ---------------------------------------------------------------------
# Request lifecycle spans
# ---------------------------------------------------------------------

def _coalesced(srv, parts):
    """Send ``parts`` as one request each into ONE engine batch."""
    srv.pause()
    results = [None] * len(parts)

    def worker(i):
        with ServeClient.connect(*srv.address) as c:
            results[i] = c.align(parts[i])

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(parts))]
    for t in threads:
        t.start()
    _wait_queued(srv, len(parts))
    srv.resume()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    return results


def _inside(child, parent):
    return (parent["ts"] <= child["ts"] + 1e-3 and
            child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3)


def test_lifecycle_spans_of_a_coalesced_batch(world):
    idx, se, _ = world
    tele = obs.Telemetry(trace=True)
    srv = AlignmentServer(idx, telemetry=tele)
    srv.start()
    try:
        parts = [se[:5], se[5:8], se[8:]]
        results = _coalesced(srv, parts)
    finally:
        srv.shutdown()
    for part, res in zip(parts, results):
        assert res.sam == offline_se(idx, part)
    evs = tele.tracer.to_dict()["traceEvents"]
    (batch,) = [e for e in evs if e["name"] == "serve.batch"]
    assert batch["args"] == {"batch": 0, "requests": 3, "reads": len(se)}
    waits = [e for e in evs if e["name"] == "serve.queue_wait"]
    assert len(waits) == 3
    assert len({(e["args"]["peer"], e["args"]["rid"]) for e in waits}) == 3
    assert sorted(e["args"]["reads"] for e in waits) == [3, 4, 5]
    for e in waits:
        assert e["args"]["batch"] == 0 and e["tid"] == batch["tid"]
        assert e["ts"] + e["dur"] <= batch["ts"] + 1e-3
    children = {}
    for e in evs:
        if e["name"] in ("serve.engine", "serve.sam", "serve.respond"):
            assert e["args"] == {"batch": 0} and _inside(e, batch)
            children[e["name"]] = e
    assert set(children) == {"serve.engine", "serve.sam", "serve.respond"}
    (smem,) = [e for e in evs if e["name"] == "smem"]
    assert _inside(smem, children["serve.engine"])
    snap = srv.metrics.snapshot()
    assert snap["time_serve.batch_s"] >= snap["time_serve.engine_s"] > 0


def test_server_telemetry_off_records_nothing(world):
    idx, se, _ = world
    tele = obs.Telemetry(trace=True)       # the compile listener is on
    srv = AlignmentServer(idx, telemetry=False)
    srv.start()
    try:
        results = _coalesced(srv, [se[:4], se[4:]])
    finally:
        srv.shutdown()
    assert all(res.sam for res in results)
    assert len(tele.tracer) == 0
    new = [k for k in srv.live_stats()
           if k.startswith(("time_", "compile", "finalize_"))]
    assert new == []
