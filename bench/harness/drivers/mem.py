"""Offline ``bwa mem``: FASTQ files through ``Aligner.stream_sam``.

Set-up writes the window's reads and the warm-up reads as FASTQ, loads
the index and runs the warm-up reads through the same ``stream_sam`` and
``open_batches`` call as the window, with the same ``-K`` chunking, so the
window finds every program compiled.  The window then streams the
window's FASTQ into a SAM file.  A batch is complete when its SAM lines
are written, which is when ``stream_sam`` asks for the next batch; the
window stops asking once it has closed, so at most the batch in flight
at the close finishes after it.  A window that reaches the end of its
reads fails the run: the files hold far more reads than a window takes.
"""

from __future__ import annotations

import time

from .. import session, traffic as tr, world
from ..context import RunContext
from ..reference import Scoring, compare
from ..spec import BENCH


class RanDry(RuntimeError):
    pass


def run(cell, ref_fa, ref, seed: int, seconds: float, trace: bool,
        t_start: float, overrides: dict | None = None, index=None):
    """Returns (RunContext, numbers compared, memory peak in bytes)."""
    import jax
    from repro.api import Aligner
    from repro.io.stream import open_batches

    cfg, trf = cell.config, cell.traffic
    spec = trf["reads"]
    work = BENCH / ".cache" / "runs" / cell.name
    work.mkdir(parents=True, exist_ok=True)
    window = tr.make_reads(spec, ref, int(spec["count"]), seed, "r")
    warm = tr.make_reads(spec, ref, int(spec["warmup"]),
                         seed + tr.WARMUP_SEED_OFFSET, "w")
    paths = tr.write_fastq(window, work, "window")
    warm_paths = tr.write_fastq(warm, work, "warm")
    paired = spec["kind"] == "pairs"
    chunk = int(cfg["reads_per_chunk"]) * int(spec["read_len"])

    idx = session.load_index(ref_fa) if index is None else index
    tracing = session.Tracing(trace, cell.name)
    aligner = Aligner(idx, session.align_options(cfg, trf, overrides),
                      telemetry=tracing.telemetry)
    with open(work / "warm.sam", "w") as out:
        aligner.stream_sam(open_batches(*warm_paths, chunk_bases=chunk),
                           out, header=False)
    ctx = RunContext(seconds=seconds)
    ctx.setup_s = time.perf_counter() - t_start

    def batches(t0):
        for b in open_batches(*paths, chunk_bases=chunk):
            yield b
            now = time.perf_counter() - t0
            ctx.completions.append((now, 2 * len(b) if paired else len(b)))
            if now >= seconds:
                return
        raise RanDry(f"the window used all {len(window.due)} reads")

    tracing.start()
    t0 = ctx.t0 = tracing.mark_window()
    summary = aligner.stream_sam(batches(t0), work / "window.sam")
    tracing.stop()

    dev = jax.devices()[0]
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    if trace:
        ctx.snapshot = dict(summary["stats"])
        ctx.reads_traced = int(summary["n_reads"])
        ctx.device, ctx.host_spans = tracing.reduce(seconds)
    del aligner, idx

    n_due = sum(n for t, n in ctx.completions if t <= seconds)
    ctx.attempted = n_due
    sim = spec["simulator"]
    insert = ((float(sim["insert_mean"]), float(sim["insert_std"]))
              if paired else None)
    with open(work / "window.sam") as f:
        numbers = compare(window.due[:n_due], f, ref,
                          Scoring.from_config(cfg["scoring"]),
                          cfg["reference"]["name"],
                          repeats=world.load_repeats(cfg["reference"]),
                          insert=insert)
    return ctx, numbers, peak
