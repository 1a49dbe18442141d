"""The alignment service: ``AlignmentServer`` in-process, open-loop
clients over loopback ``ServeClient`` connections.

Each request is due at a time fixed by the traffic file and the seed
(``traffic.arrival_times``).  A dispatcher hands each request to a pool
of client threads, one connection each, at its due time; a request's
latency runs from when it was due to when its last frame arrived, so a
late send counts against the server.  The window closes after the last
request is due; the run then waits for every answer, a minute at most.
A request answered with an error frame is failed; one never answered is
missing, which the correctness check counts.

Besides the plain reference's numbers, the check holds the service to
its own guarantee: once the server is down, the answered reads go
through an offline ``Aligner.stream_sam`` on the same index and options,
and ``offline_diff`` counts the requests whose SAM lines differ from
that run's lines for their reads, byte for byte.

Set-up warms the engine at the coalesced batch widths listed in the
traffic file (``warmup.widths``): the server is paused, that many
warm-up requests are queued, and it is resumed, so they run as one
engine batch.
"""

from __future__ import annotations

import queue
import sys
import threading
import time

from .. import session, traffic as tr, world
from ..context import RunContext
from ..reference import Scoring, compare
from ..spec import BENCH

DRAIN_S = 60.0


class _Pool:
    """Client threads, one ``ServeClient`` connection each, sending the
    requests handed to ``submit`` and recording when each answer came."""

    def __init__(self, host, port, n: int, timeout: float):
        from repro.serve import ServeClient
        self.todo: queue.Queue = queue.Queue()
        self.lock = threading.Lock()
        self.done: dict = {}
        self.clients = [ServeClient.connect(host, port, timeout=timeout)
                        for _ in range(n)]
        self.threads = [threading.Thread(target=self._work, args=(c,),
                                         daemon=True)
                        for c in self.clients]
        for t in self.threads:
            t.start()

    def _work(self, client) -> None:
        from repro.serve import ServeError
        while True:
            item = self.todo.get()
            if item is None:
                return
            key, reads, sent = item
            try:
                res = client.align(reads)
                out = ("ok", res.sam)
            except ServeError as e:
                out = ("error", e.code)
            except OSError as e:
                out = ("error", type(e).__name__)
            with self.lock:
                self.done[key] = (time.perf_counter(), out, sent)

    def submit(self, key, reads) -> None:
        self.todo.put((key, reads, time.perf_counter()))

    def wait(self, keys, until: float) -> None:
        while time.perf_counter() < until:
            with self.lock:
                if all(k in self.done for k in keys):
                    return
            time.sleep(0.01)

    def close(self) -> None:
        for _ in self.threads:
            self.todo.put(None)
        for c in self.clients:
            c.close()
        for t in self.threads:
            t.join(timeout=5.0)


def _warm(server, pool, warm, widths) -> None:
    seqs = tr.seq_strings(warm.ends[0])
    at = 0
    for w in widths:
        keys = [("warm", at + i) for i in range(w)]
        accepted = server.metrics.snapshot().get("serve_requests", 0)
        server.pause()
        for i, k in enumerate(keys):
            pool.submit(k, [(warm.names[at + i], seqs[at + i])])
        # the paused scheduler holds the first request it took outside the
        # queue, so w - 1 queued means all w are in
        t_end = time.perf_counter() + 30.0
        while time.perf_counter() < t_end and not (
                server.metrics.snapshot().get("serve_requests", 0)
                >= accepted + w and len(server.queue) >= w - 1):
            time.sleep(0.005)
        server.resume()
        pool.wait(keys, time.perf_counter() + 600.0)
        at += w


def run(cell, ref_fa, ref, seed: int, seconds: float, trace: bool,
        t_start: float, overrides: dict | None = None, index=None):
    """Returns (RunContext, numbers compared, memory peak in bytes)."""
    import jax
    from repro.serve import AlignmentServer

    cfg, trf = cell.config, cell.traffic
    spec, arr = trf["reads"], trf["arrivals"]
    k = int(arr["reads_per_request"])
    due_at = tr.arrival_times(arr, seconds, seed)
    n_req = len(due_at)
    window = tr.make_reads(spec, ref, n_req * k, seed, "q")
    widths = [int(w) for w in trf["warmup"]["widths"]]
    warm = tr.make_reads(spec, ref, sum(widths),
                         seed + tr.WARMUP_SEED_OFFSET, "w")
    seqs = tr.seq_strings(window.ends[0])
    reqs = [[(window.names[i * k + j], seqs[i * k + j]) for j in range(k)]
            for i in range(n_req)]

    idx = session.load_index(ref_fa) if index is None else index
    tracing = session.Tracing(trace, cell.name)
    srv = cfg["server"]
    server = AlignmentServer(idx, session.align_options(cfg, trf, overrides),
                             max_batch_reads=int(srv["max_batch_reads"]),
                             max_queue=int(srv["max_queue"]),
                             telemetry=tracing.telemetry or False)
    host, port = server.start()
    pool = None
    try:
        pool = _Pool(host, port, int(trf["clients"]), DRAIN_S + seconds)
        _warm(server, pool, warm, widths)
        ctx = RunContext(seconds=seconds)
        ctx.setup_s = time.perf_counter() - t_start
        before_stats = server.live_stats() if trace else None
        before_width = _width(server)
        before_hist = _hist(server)

        tracing.start()
        t0 = ctx.t0 = tracing.mark_window()
        late = 0.0
        for i, d in enumerate(due_at):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            else:
                late = max(late, -wait)
            pool.submit(("q", i), reqs[i])
        tracing.stop()
        pool.wait([("q", i) for i in range(n_req)],
                  t0 + seconds + DRAIN_S)
        print(f"serve: latest send {late * 1e3:.3f} ms after its due time",
              file=sys.stderr, flush=True)
        with pool.lock:
            done = dict(pool.done)
        dev = jax.devices()[0]
        peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
        n1, s1 = _width(server)
        ctx.coalesce = (n1 - before_width[0], s1 - before_width[1])
        ctx.widths = [(edge, a - b) for (edge, a), (_, b)
                      in zip(_hist(server), before_hist) if a > b]
        h = server.metrics.snapshot().get("serve_coalesce_width")
        ctx.widths.append(("max so far", None if h is None else h.vmax))
        if trace:
            after = server.live_stats()
            ctx.snapshot = _delta(after, before_stats)
            ctx.reads_traced = int(ctx.snapshot.get("serve_reads", 0))
            ctx.device, ctx.host_spans = tracing.reduce(seconds)
    finally:
        server.shutdown(drain=False)
        if pool is not None:
            pool.close()
    del server

    lines, due, answered = [], [], {}
    for i in range(n_req):
        got = done.get(("q", i))
        if got is None:                      # never answered: missing
            ctx.latencies_ms.append(None)
            ctx.failed += 1
            due.extend(window.due[i * k:(i + 1) * k])
            continue
        t_done, (status, payload), _ = got
        if status != "ok":                   # refused or errored
            ctx.latencies_ms.append(None)
            ctx.failed += 1
            continue
        ctx.latencies_ms.append((t_done - (t0 + due_at[i])) * 1e3)
        lines.extend(payload)
        answered[i] = payload
        due.extend(window.due[i * k:(i + 1) * k])
    ctx.attempted = n_req
    numbers = compare(due, lines, ref, Scoring.from_config(cfg["scoring"]),
                      cfg["reference"]["name"],
                      repeats=world.load_repeats(cfg["reference"]))
    numbers["offline_diff"] = _offline_diff(
        idx, session.align_options(cfg, trf, overrides), reqs, answered,
        int(srv["max_batch_reads"]), cell.name)
    del idx
    return ctx, numbers, peak


def _offline_diff(idx, options, reqs, answered: dict, width: int,
                  name: str) -> int:
    """Requests whose answer differs from an offline ``stream_sam`` of
    their reads (SAM lines without the header), in batches of ``width``
    reads."""
    from repro.api import Aligner
    from repro.io.stream import open_batches
    work = BENCH / ".cache" / "runs" / name
    work.mkdir(parents=True, exist_ok=True)
    fq, sam = work / "offline.fq", work / "offline.sam"
    with open(fq, "w") as f:
        for i in sorted(answered):
            for qname, seq in reqs[i]:
                f.write(f"@{qname}\n{seq}\n+\n{'I' * len(seq)}\n")
    Aligner(idx, options).stream_sam(open_batches(fq, batch_size=width),
                                     sam, header=False)
    by_name: dict = {}
    with open(sam) as f:
        for ln in f:
            by_name.setdefault(ln.split("\t", 1)[0], []).append(
                ln.rstrip("\n"))
    return sum(1 for i, got in answered.items()
               if list(got) != [ln for qname, _ in reqs[i]
                                for ln in by_name.get(qname, [])])


def _width(server) -> tuple[int, float]:
    """(engine batches, requests in them) so far, from the server's
    ``serve_coalesce_width`` histogram."""
    h = server.metrics.snapshot().get("serve_coalesce_width")
    return (0, 0.0) if h is None else (int(h.count), float(h.total))


def _hist(server) -> list:
    """[(upper edge, engine batches)] of the ``serve_coalesce_width``
    histogram so far, the last edge ``inf``."""
    h = server.metrics.snapshot().get("serve_coalesce_width")
    if h is None:
        return []
    return list(zip(list(h.edges) + [float("inf")], h.counts))


def _delta(after: dict, before: dict) -> dict:
    """Numeric counters of ``after`` less those of ``before``."""
    out = {}
    for key, v in after.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            b = before.get(key, 0)
            out[key] = v - (b if isinstance(b, (int, float)) else 0)
    return out

