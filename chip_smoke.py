#!/usr/bin/env python3
"""Smoke test of the aligner's device path on a TPU.

Drives the normal entry points in ONE process (the chip belongs to one
process at a time): ``repro.cli.main`` for ``index`` and ``mem``, and an
in-process ``repro.serve.AlignmentServer`` with ``ServeClient`` requests
over loopback.  The reference is a seeded, repeat-rich simulation at
GRCh38 chr21 scale (46.7 Mbp; the FM-index is the state that lives on the
device), and about 512 seeded 2x150 read pairs exercise every path.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # memdist -n 4 vs unsharded mem

Checks (one chip):

* ``mem --engine pallas`` and ``--engine batched`` write byte-identical
  SAM, and both match ``--engine baseline`` on the first 32 pairs (all
  runs use ``-K`` with ``--pe-bootstrap``, so the insert-size stats come
  from the same leading chunk of 32 pairs);
* the Pallas kernels ran compiled: the occ sweep's config is not in
  interpret mode, both kernel dispatch counters of the profile are
  positive, the run log's manifest says ``compiled``, and no warning
  about forced interpret mode was raised;
* every serve response equals offline ``Aligner.stream_sam`` over the
  same reads, with no error frame.

With ``--chips 4`` it runs only ``memdist -n 4`` against unsharded
``mem`` (byte-identical SAM) and prints the device of each shard.

Earlier lines report phase wall times, the occ sweep, device memory and
a smoke rate (not a benchmark).  The last line is one JSON object,
printed only when every check passed; any failure exits non-zero.
The script finds no CPU fallback: without a TPU it exits 1 at once.
Everything it writes goes to ``.smoke/`` in the checkout (git-ignored).
"""

from __future__ import annotations

import argparse
import io
import json
import pathlib
import shutil
import statistics
import sys
import time
import warnings

ROOT = pathlib.Path(__file__).resolve().parent
CHR21_BP = 46_709_983          # GRCh38 chr21
READ_LEN = 150
HEAD_PAIRS = 32                # pairs compared against the baseline engine
K_BASES = HEAD_PAIRS * 2 * READ_LEN  # -K: one chunk == the first 32 pairs
RG = "@RG\\tID:smoke"
#: (reference bp, 2x150 pairs) per --chips: one chip holds the chr21-scale
#: index; four chips take one -K chunk of pairs per shard on a reference
#: cut to keep the four index builds and uploads short
SIZES = {1: (CHR21_BP, 512), 4: (8_000_000, 128)}


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"check ok: {what}", flush=True)


def log(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Wall time per phase, printed as each one ends."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def __call__(self, name: str, start: float) -> float:
        dt = time.perf_counter() - start
        log(f"phase {name}: {dt:.3f} s (elapsed {time.perf_counter() - self.t0:.1f} s)")
        return dt


def _cli(argv: list[str]) -> None:
    from repro import cli
    rc = cli.main(argv)
    if rc != 0:
        raise SmokeFailure(f"repro.cli {' '.join(argv[:1])} exited {rc}")


def make_data(out: pathlib.Path, ref_bp: int, n_pairs: int, seed: int):
    """Seeded reference FASTA + 2x150 paired FASTQ (and its first 32
    pairs as a second pair of files)."""
    from repro.data import simulate_pairs, simulate_reads, simulate_reference
    from repro.data.reads import write_fasta, write_fastq_pair
    contigs = simulate_reference(ref_bp, 1, seed=seed, names=["chr21"])
    fa = out / "ref.fa"
    write_fasta(fa, contigs)
    ref = contigs[0][1]
    r1, r2, _ = simulate_pairs(ref, n_pairs, READ_LEN, insert_mean=400,
                               insert_std=50, seed=seed + 1, burst_frac=0.05)
    fq = (out / "r1.fq", out / "r2.fq")
    head = (out / "r1.head.fq", out / "r2.head.fq")
    write_fastq_pair(*fq, r1, r2)
    write_fastq_pair(*head, r1[:HEAD_PAIRS], r2[:HEAD_PAIRS])
    se, _ = simulate_reads(ref, 48, READ_LEN, seed=seed + 2)
    return fa, fq, head, (r1, r2, se)


def _no_interpret_warnings(caught, runlog_events) -> bool:
    msgs = [str(w.message) for w in caught
            if issubclass(w.category, RuntimeWarning)]
    msgs += [e["message"] for e in runlog_events
             if e["event"] == "warning" and e.get("category") == "RuntimeWarning"]
    return not any("interpret mode" in m for m in msgs)


def mem_run(phases, out, fa, fq, engine: str, *, profile=True):
    """One ``repro.cli mem`` run; returns (sam_bytes, wall_s, profile,
    runlog events)."""
    sam = out / f"{engine}.sam"
    argv = ["mem", str(fa), str(fq[0]), str(fq[1]), "--engine", engine,
            "-Y", "-R", RG, "--no-pg", "-K", str(K_BASES), "--pe-bootstrap",
            "-o", str(sam)]
    prof = out / f"{engine}.prof.json"
    if profile:
        argv += ["--profile", str(prof)]
    t = time.perf_counter()
    _cli(argv)
    wall = phases(f"mem --engine {engine} ({fq[0].name})", t)
    if not profile:
        return sam.read_bytes(), wall, None, []
    from repro import obs
    return (sam.read_bytes(), wall, obs.read_profile(prof),
            obs.read_runlog(out / f"{engine}.prof.runlog.jsonl"))


def report_batches(engine: str, events: list[dict], n_pairs: int,
                   wall: float) -> None:
    batches = [e["batch_s"] for e in events if e["event"] == "batch"]
    if not batches:
        return
    warm = batches[1:] or batches
    log(f"{engine}: {len(batches)} batches; first batch (compile) "
        f"{batches[0]:.3f} s; warm batch median {statistics.median(warm):.3f} s "
        f"(min {min(warm):.3f}, max {max(warm):.3f})")
    log(f"{engine}: smoke rate {n_pairs / wall:.2f} read pairs/s over the "
        f"whole mem call incl. compile and index load (a smoke rate, not a "
        f"benchmark)")


def run_one_chip(out: pathlib.Path, ref_bp: int, n_pairs: int,
                 seed: int) -> None:
    import jax
    from repro.api import Aligner
    from repro.io.store import load_index
    from repro.io.stream import _pack_pe, _pack_se
    from repro.kernels.config import default_interpret
    from repro.kernels.engine import attach_occ_config
    from repro.options import AlignOptions
    from repro.serve import AlignmentServer, ServeClient, ServeError
    from repro.data import decode

    phases = Phases()
    dev = jax.devices()[0]
    itp = default_interpret()

    def mem_stats(when: str) -> None:
        st = dev.memory_stats() or {}
        log(f"device memory {when}: peak_bytes_in_use="
            f"{st.get('peak_bytes_in_use', 'not reported')} bytes_in_use="
            f"{st.get('bytes_in_use', 'not reported')}")

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")

        t = time.perf_counter()
        fa, fq, head, (r1, r2, se) = make_data(out, ref_bp, n_pairs, seed)
        phases("reference + reads generation", t)
        t = time.perf_counter()
        _cli(["index", str(fa)])
        phases("index build (repro.cli index)", t)

        t = time.perf_counter()
        idx = load_index(fa)
        phases("index load (host)", t)
        t = time.perf_counter()
        fm = jax.block_until_ready(idx.device())
        phases("index upload", t)
        unpadded = sum(int(a.nbytes) for a in fm)
        log(f"FMArrays unpadded bytes: {unpadded} "
            f"({unpadded / ref_bp:.2f} B per reference base)")
        mem_stats("after upload")

        t = time.perf_counter()
        cfg = attach_occ_config(idx)
        phases("occ layout sweep", t)
        for layout, qb, best in cfg.timings:
            log(f"occ sweep: {layout} qb={qb}: best {best * 1e3:.3f} ms "
                f"per 2048 queries")
        log(f"occ sweep pick: {cfg.layout} qb={cfg.qb} interpret={cfg.interpret}")
        check(cfg.interpret == itp, f"occ kernel interpret={cfg.interpret}")

        sam_p, wall_p, prof_p, rl_p = mem_run(phases, out, fa, fq, "pallas")
        report_batches("pallas", rl_p, n_pairs, wall_p)
        snap = prof_p["snapshot"]
        n_bsw = snap.get("kernel_bsw_dispatches", 0)
        n_occ = snap.get("kernel_fmocc_dispatches", 0)
        log(f"pallas profile: kernel_bsw_dispatches={n_bsw} "
            f"kernel_fmocc_dispatches={n_occ}")
        check(n_bsw > 0 and n_occ > 0, "both Pallas kernels were dispatched")
        start = next(e for e in rl_p if e["event"] == "run_start")
        log(f"pallas run_start device: {json.dumps(start['device'])}")
        check(start["device"]["kernel_mode"] == ("interpret" if itp else "compiled")
              and start["device"]["platform"] == dev.platform,
              "run log manifest records the device and kernel mode")

        sam_b, wall_b, _, rl_b = mem_run(phases, out, fa, fq, "batched")
        report_batches("batched", rl_b, n_pairs, wall_b)
        check(sam_p == sam_b, "pallas SAM == batched SAM (byte-identical)")

        sam_0, _, _, _ = mem_run(phases, out, fa, head, "baseline",
                                 profile=False)
        head_names = {f"pair{i}" for i in range(HEAD_PAIRS)}
        lines = sam_p.decode().splitlines()
        subset = [ln for ln in lines
                  if ln.startswith("@") or ln.split("\t", 1)[0] in head_names]
        check(subset == sam_0.decode().splitlines(),
              f"pallas/batched SAM == baseline SAM on the first {HEAD_PAIRS} pairs")
        mem_stats("after the mem runs")

        # ---- serve: in-process server on the loaded index ----
        t = time.perf_counter()
        opts = AlignOptions(engine="pallas")
        se_items = [(f"se{i}", decode(r)) for i, r in enumerate(se)]
        pe_items = [(f"pp{i}", decode(a), decode(b))
                    for i, (a, b) in enumerate(zip(r1[-32:], r2[-32:]))]
        se_reqs = [se_items[i:i + 16] for i in range(0, len(se_items), 16)]
        pe_reqs = [pe_items[:16], pe_items[16:]]
        server = AlignmentServer(idx, opts)
        host, port = server.start()
        try:
            with ServeClient.connect(host, port, timeout=600) as c:
                se_res = [c.align(r) for r in se_reqs]
                pe_res = [c.align_pairs(r) for r in pe_reqs]
        except ServeError as e:
            raise SmokeFailure(f"serve returned an error frame: {e}")
        finally:
            server.shutdown()
        phases("serve (5 requests)", t)
        n_err = server.metrics.snapshot().get("serve_errors", 0)
        check(n_err == 0, "serve: no error frames")

        def offline(items, pack):
            buf = io.StringIO()
            Aligner(idx, opts).stream_sam([pack(*zip(*items))], buf,
                                          header=False)
            return buf.getvalue().splitlines()

        same = all(res.sam == offline(req, _pack_se)
                   for req, res in zip(se_reqs, se_res))
        same &= all(res.sam == offline(req, _pack_pe)
                    for req, res in zip(pe_reqs, pe_res))
        check(same, f"serve: {len(se_reqs)} SE + {len(pe_reqs)} PE responses "
                    f"== offline stream_sam")
        mem_stats("at the end")

    check(_no_interpret_warnings(caught, rl_p + rl_b),
          "no warning about Pallas kernels forced into interpret mode")


def run_four_chips(out: pathlib.Path, ref_bp: int, n_pairs: int,
                   seed: int) -> None:
    phases = Phases()
    t = time.perf_counter()
    fa, fq, _, _ = make_data(out, ref_bp, n_pairs, seed)
    phases("reference + reads generation", t)
    t = time.perf_counter()
    _cli(["index", str(fa)])
    phases("index build (repro.cli index)", t)
    common = ["-K", str(K_BASES), "--engine", "pallas", "--no-pg"]
    t = time.perf_counter()
    _cli(["memdist", str(fa), str(fq[0]), str(fq[1]), "-n", "4",
          "-o", str(out / "memdist.sam"), "--runlog",
          str(out / "memdist.runlog.jsonl"), *common])
    phases("memdist -n 4", t)
    t = time.perf_counter()
    _cli(["mem", str(fa), str(fq[0]), str(fq[1]), "--pe-bootstrap",
          "-o", str(out / "mem.sam"), *common])
    phases("mem (unsharded)", t)
    from repro import obs
    events = obs.read_runlog(out / "memdist.runlog.jsonl")
    shards = {e["shard"]: e["device"]
              for e in events if e["event"] == "shard_start"}
    for s in sorted(shards):
        log(f"memdist shard {s}: device {shards[s]}")
    check(len(shards) == 4 and len(set(shards.values())) == 4,
          "the 4 shards ran on 4 devices")
    # a retried shard would hide a device fault that cleared on a rerun
    retried = [e for e in events
               if e["event"] in ("shard_retry", "shard_abandoned")]
    check(not retried, "memdist: no shard was retried or abandoned")
    check((out / "memdist.sam").read_bytes() == (out / "mem.sam").read_bytes(),
          "memdist -n 4 SAM == unsharded mem SAM (byte-identical)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip smoke; 4: memdist across the "
                         "four chips of one host [1]")
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is "
              f"{devices[0].platform!r}; this smoke has no CPU fallback",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: no src/repro next to this script; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.config import default_interpret, enable_compile_cache
    if default_interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode on "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    log(f"device: {devices[0].device_kind} x{len(devices)}; "
        f"compile cache: {cache}")

    ref_bp, n_pairs = SIZES[args.chips]
    log(f"reference: {ref_bp} bp"
        + ("" if ref_bp == CHR21_BP else
           f" (cut from GRCh38 chr21's {CHR21_BP} bp)")
        + f"; {n_pairs} read pairs")
    out = ROOT / ".smoke"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir()
    run = run_one_chip if args.chips == 1 else run_four_chips
    try:
        run(out, ref_bp, n_pairs, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    if cache:
        n = sum(1 for p in pathlib.Path(cache).rglob("*") if p.is_file())
        log(f"compile cache {cache}: {n} files")
    shutil.rmtree(out, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
