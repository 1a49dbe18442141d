"""I/O subsystem tests: FASTA/FASTQ round-trips (plain and gzipped,
hypothesis-backed), the on-disk index bundle, the streaming batcher with
its dist shard filter, and the acceptance bar — ``repro.cli index`` +
``mem`` end-to-end on a gzipped 3-contig reference with gzipped paired
FASTQ, byte-identical to driving ``align_pairs_optimized`` in memory on
the same data through a ``load_index`` round-trip."""

import gzip

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro import cli
from repro.core import build_contig_index, sam_header
from repro.core.fmindex import PERSIST_ARRAYS, build_index
from repro.core.pipeline import (align_pairs_optimized,
                                 align_reads_optimized, to_sam)
from repro.data import (make_reference, simulate_pairs_multi,
                        simulate_reference, write_fasta, write_fastq_pair)
from repro.dist.api import read_shard
from repro.io import (FastqRecord, encode_read, have_index, load_index,
                      load_reference, read_fasta, read_fastq,
                      read_fastq_interleaved, read_fastq_paired, save_index,
                      stream_batches, stream_pair_batches)
from repro.io import fasta as iofasta
from repro.io import fastq as iofastq
from repro.io import store as iostore

N_PAIRS = 48
L = 101


# ---------------------------------------------------------------------
# world: a 3-contig reference + paired reads, on disk and in memory
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def world(tmp_path_factory):
    d = tmp_path_factory.mktemp("io_world")
    contigs = simulate_reference(24_000, 3, seed=3)
    r1, r2, truth = simulate_pairs_multi(contigs, N_PAIRS, L, seed=4,
                                         insert_mean=300, insert_std=30,
                                         burst_frac=0.1)
    fa = str(d / "ref.fa.gz")
    fq1, fq2 = str(d / "reads_1.fq.gz"), str(d / "reads_2.fq.gz")
    write_fasta(fa, contigs)
    write_fastq_pair(fq1, fq2, r1, r2)
    return dict(dir=d, contigs=contigs, r1=r1, r2=r2, truth=truth,
                fa=fa, fq1=fq1, fq2=fq2)


@pytest.fixture(scope="module")
def indexed(world):
    """CLI-built on-disk bundle + its load_index round-trip."""
    assert cli.main(["index", world["fa"]]) == 0
    assert have_index(world["fa"])
    return load_index(world["fa"])


# ---------------------------------------------------------------------
# FASTA
# ---------------------------------------------------------------------

@pytest.mark.parametrize("name", ["plain.fa", "zipped.fa.gz"])
def test_fasta_roundtrip(tmp_path, name):
    recs = [("chr1", "ACGTACGTACGTN" * 7), ("chr2 extra words", "acgtn"),
            ("chr3", "A")]
    path = str(tmp_path / name)
    iofasta.write_fasta(path, recs, width=10)
    back = read_fasta(path)
    assert back == [("chr1", recs[0][1]), ("chr2", "acgtn"), ("chr3", "A")]
    if name.endswith(".gz"):       # really gzipped on disk
        with open(path, "rb") as f:
            assert f.read(2) == b"\x1f\x8b"


def test_fasta_gzip_sniffing(tmp_path):
    """A gzipped file without the .gz suffix still reads (magic sniff)."""
    path = str(tmp_path / "misnamed.fa")
    with gzip.open(path, "wt") as f:
        f.write(">c\nACGT\n")
    assert read_fasta(path) == [("c", "ACGT")]


def test_fasta_errors(tmp_path):
    p = tmp_path / "bad.fa"
    p.write_text("ACGT\n")
    with pytest.raises(ValueError, match="before first"):
        read_fasta(str(p))
    p.write_text("")
    with pytest.raises(ValueError, match="no FASTA records"):
        read_fasta(str(p))


def test_reference_ambiguity_seeded(tmp_path):
    """IUPAC letters become random ACGT under the fixed seed: loads are
    deterministic, in 0..3, and track the seed (bwa's srand48(11))."""
    path = str(tmp_path / "amb.fa")
    iofasta.write_fasta(path, [("c1", "ANNNRYSWKMBDHVACGT"), ("c2", "NNNN")])
    a = load_reference(path)
    b = load_reference(path)
    assert all(np.array_equal(x[1], y[1]) for x, y in zip(a, b))
    assert all(int(arr.max()) <= 3 for _, arr in a)
    # unambiguous positions are untouched
    assert a[0][1][0] == 0 and list(a[0][1][-4:]) == [0, 1, 2, 3]
    c = load_reference(path, seed=12)
    assert any(not np.array_equal(x[1], y[1]) for x, y in zip(a, c))
    with pytest.raises(ValueError, match="invalid reference character"):
        iofasta.encode_reference("ACG-T", np.random.default_rng(0))


def test_write_fasta_simulator_contigs_reingest(world):
    """data.write_fasta -> io.load_reference reproduces the simulated
    contigs exactly (no ambiguity in simulator output)."""
    back = load_reference(world["fa"])
    assert [n for n, _ in back] == [n for n, _ in world["contigs"]]
    for (_, want), (_, got) in zip(world["contigs"], back):
        assert np.array_equal(want, got)


# ---------------------------------------------------------------------
# FASTQ
# ---------------------------------------------------------------------

@pytest.mark.parametrize("name", ["r.fq", "r.fq.gz"])
def test_fastq_roundtrip(tmp_path, name):
    recs = [FastqRecord("a/1", "ACGTN", "IIII#"),
            FastqRecord("b", "acgt", "!~:,")]
    path = str(tmp_path / name)
    iofastq.write_fastq(path, recs)
    assert list(read_fastq(path)) == recs


def test_fastq_malformed(tmp_path):
    p = tmp_path / "bad.fq"
    p.write_text("@r1\nACGT\nIIII\n")               # '+' line missing
    with pytest.raises(ValueError, match=r"\+"):
        list(read_fastq(str(p)))
    p.write_text("@r1\nACGT\n+\nIII\n")             # qual too short
    with pytest.raises(ValueError, match="quality length"):
        list(read_fastq(str(p)))
    p.write_text("r1\nACGT\n+\nIIII\n")             # header not @
    with pytest.raises(ValueError, match="malformed"):
        list(read_fastq(str(p)))


def test_fastq_pair_sync(tmp_path):
    p1, p2 = str(tmp_path / "a_1.fq"), str(tmp_path / "a_2.fq")
    iofastq.write_fastq(p1, [FastqRecord("x/1", "ACGT", "IIII"),
                             FastqRecord("y/1", "ACGT", "IIII")])
    iofastq.write_fastq(p2, [FastqRecord("x/2", "ACGT", "IIII")])
    with pytest.raises(ValueError, match="different record counts"):
        list(read_fastq_paired(p1, p2))
    iofastq.write_fastq(p2, [FastqRecord("x/2", "ACGT", "IIII"),
                             FastqRecord("z/2", "ACGT", "IIII")])
    with pytest.raises(ValueError, match="out of sync"):
        list(read_fastq_paired(p1, p2))


def test_fastq_interleaved(tmp_path):
    p = str(tmp_path / "il.fq")
    iofastq.write_fastq(p, [FastqRecord("x/1", "AC", "II"),
                            FastqRecord("x/2", "GT", "II")])
    pairs = list(read_fastq_interleaved(p))
    assert len(pairs) == 1 and pairs[0][0].name == "x/1"
    iofastq.write_fastq(p, [FastqRecord("x/1", "AC", "II"),
                            FastqRecord("x/2", "GT", "II"),
                            FastqRecord("y/1", "AC", "II")])
    with pytest.raises(ValueError, match="odd record count"):
        list(read_fastq_interleaved(p))


def test_encode_read():
    got = encode_read("ACGTacgtNRX")
    assert list(got) == [0, 1, 2, 3, 0, 1, 2, 3, 4, 4, 4]


def test_write_fastq_pair_suffixes(world):
    recs1 = list(read_fastq(world["fq1"]))
    recs2 = list(read_fastq(world["fq2"]))
    assert [r.name for r in recs1[:2]] == ["pair0/1", "pair1/1"]
    assert [r.name for r in recs2[:2]] == ["pair0/2", "pair1/2"]
    assert np.array_equal(encode_read(recs1[3].seq), world["r1"][3])


# ---------------------------------------------------------------------
# hypothesis round-trip properties
# ---------------------------------------------------------------------

_name = st.text(st.characters(min_codepoint=33, max_codepoint=126,
                              exclude_characters="@>"),
                min_size=1, max_size=12)
_seq = st.text(st.sampled_from("ACGTNacgtnRYSWKMbdhv"), min_size=1,
               max_size=80)


@st.composite
def _fastq_record(draw):
    seq = draw(_seq)
    qual = draw(st.text(st.characters(min_codepoint=33,
                                      max_codepoint=126),
                        min_size=len(seq), max_size=len(seq)))
    return FastqRecord(draw(_name), seq, qual)


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(_name, _seq), min_size=1, max_size=6),
       st.booleans(), st.integers(1, 90))
def test_property_fasta_roundtrip(tmp_path_factory, recs, gz, width):
    d = tmp_path_factory.mktemp("hfa")
    path = str(d / ("x.fa.gz" if gz else "x.fa"))
    iofasta.write_fasta(path, recs, width=width)
    assert read_fasta(path) == [(n, s) for n, s in recs]


@settings(max_examples=15, deadline=None)
@given(st.lists(_fastq_record(), min_size=1, max_size=6), st.booleans())
def test_property_fastq_roundtrip(tmp_path_factory, recs, gz):
    d = tmp_path_factory.mktemp("hfq")
    path = str(d / ("x.fq.gz" if gz else "x.fq"))
    iofastq.write_fastq(path, recs)
    assert list(read_fastq(path)) == recs


# ---------------------------------------------------------------------
# index bundle (store)
# ---------------------------------------------------------------------

def test_store_roundtrip_contig(world, indexed):
    built = build_contig_index(world["contigs"])
    loaded = indexed
    for k in PERSIST_ARRAYS:
        a, b = getattr(built, k), getattr(loaded, k)
        assert a.dtype == b.dtype and np.array_equal(a, b), k
    for k in ("n_ref", "N", "primary"):
        assert getattr(built, k) == getattr(loaded, k)
    assert np.array_equal(built._occ_prefix, loaded._occ_prefix)
    assert loaded.names == built.names
    assert np.array_equal(loaded.offsets, built.offsets)
    assert np.array_equal(loaded.lengths, built.lengths)
    assert np.array_equal(loaded.edges, built.edges)
    assert sam_header(loaded) == sam_header(built)


def test_store_roundtrip_plain(tmp_path):
    """A single-sequence FMIndex (no contig table) also round-trips and
    keeps its degenerate-C=1 SAM behaviour."""
    idx = build_index(make_reference(3000, seed=1))
    prefix = str(tmp_path / "plain")
    save_index(prefix, idx)
    back = load_index(prefix)
    assert not hasattr(back, "names") or getattr(back, "names", None) in ((), None)
    for k in PERSIST_ARRAYS:
        assert np.array_equal(getattr(idx, k), getattr(back, k)), k
    assert sam_header(back) == [sam_header(idx)[0],
                                f"@SQ\tSN:ref\tLN:{idx.n_ref}"]


def test_store_versioning_and_errors(tmp_path, world, indexed):
    with pytest.raises(FileNotFoundError, match="no index bundle"):
        load_index(str(tmp_path / "nope"))
    jp, _ = iostore.index_paths(world["fa"])
    meta = jp.read_text()
    try:
        jp.write_text(meta.replace('"version": 1', '"version": 999'))
        with pytest.raises(ValueError, match="version"):
            load_index(world["fa"])
        jp.write_text(meta.replace(iostore.INDEX_FORMAT, "something-else"))
        with pytest.raises(ValueError, match="not a"):
            load_index(world["fa"])
    finally:
        jp.write_text(meta)


# ---------------------------------------------------------------------
# streaming batcher + shard filter
# ---------------------------------------------------------------------

def test_stream_batches_shapes(world):
    batches = list(stream_batches(world["fq1"], 20))
    assert [len(b) for b in batches] == [20, 20, 8]
    assert all(b.reads.shape[1] == L for b in batches)
    whole = np.concatenate([b.reads for b in batches])
    assert np.array_equal(whole, world["r1"])
    assert batches[0].names[0] == "pair0/1"
    assert (batches[0].lens == L).all()


def test_stream_mixed_lengths_padded(tmp_path):
    p = str(tmp_path / "mix.fq")
    iofastq.write_fastq(p, [FastqRecord("a", "ACGT", "IIII"),
                            FastqRecord("b", "AC", "II")])
    (b,) = stream_batches(p, 8)
    assert b.reads.shape == (2, 4)
    assert list(b.lens) == [4, 2]
    assert list(b.reads[1]) == [0, 1, 4, 4]        # PAD_CODE = 4 tail


def test_stream_pair_asymmetric_lengths_shared_width(tmp_path):
    """R1/R2 of different lengths (e.g. asymmetric trimming) pad to ONE
    shared width so the PE driver can stack them into a single batch."""
    p1, p2 = str(tmp_path / "a_1.fq"), str(tmp_path / "a_2.fq")
    iofastq.write_fastq(p1, [FastqRecord("x/1", "ACGTACGTAC", "I" * 10)])
    iofastq.write_fastq(p2, [FastqRecord("x/2", "ACGTAC", "I" * 6)])
    (b,) = stream_pair_batches(p1, p2, 8)
    assert b.reads1.shape == b.reads2.shape == (1, 10)
    assert list(b.lens1) == [10] and list(b.lens2) == [6]
    assert list(b.reads2[0][6:]) == [4, 4, 4, 4]
    np.concatenate([b.reads1, b.reads2], axis=0)   # what the driver does


def test_open_text_closes_raw_handle(tmp_path):
    """The gzip sniffing path must not leak the raw fd (GzipFile does not
    close a caller-provided fileobj)."""
    import gc
    path = str(tmp_path / "x.fa.gz")
    iofasta.write_fasta(path, [("c", "ACGT")])
    f = iofasta.open_text(path)
    f.read()
    f.close()
    gc.collect()
    fds = [p for p in __import__("pathlib").Path("/proc/self/fd").iterdir()
           if p.resolve().name == "x.fa.gz"] \
        if __import__("os").path.isdir("/proc/self/fd") else []
    assert fds == []


def test_stream_pair_batches_synchronized(world):
    batches = list(stream_pair_batches(world["fq1"], world["fq2"], 32))
    assert [len(b) for b in batches] == [32, 16]
    assert batches[0].names[:2] == ["pair0", "pair1"]
    r1 = np.concatenate([b.reads1 for b in batches])
    r2 = np.concatenate([b.reads2 for b in batches])
    assert np.array_equal(r1, world["r1"]) and np.array_equal(r2, world["r2"])


def test_shard_partition_disjoint_and_covering(world):
    """Shards (i, n) are disjoint, cover every pair, and are independent
    of batch size; mates stay on one shard."""
    n = 3
    seen = {}
    for i in range(n):
        for bs in (7, 64):
            names = [nm for b in stream_pair_batches(
                world["fq1"], world["fq2"], bs, shard=(i, n))
                for nm in b.names]
            seen.setdefault(i, names)
            assert names == seen[i]              # batch-size independent
        assert seen[i] == [f"pair{k}" for k in range(i, N_PAIRS, n)]
    allnames = sorted(sum(seen.values(), []), key=lambda s: int(s[4:]))
    assert allnames == [f"pair{k}" for k in range(N_PAIRS)]
    with pytest.raises(ValueError, match="bad shard"):
        list(stream_batches(world["fq1"], 8, shard=(3, 3)))


def test_read_shard_spec():
    assert read_shard("2/5") == (2, 5)
    assert read_shard(None) == (0, 1)            # single-process fallback
    for bad in ("5/5", "x/2", "3"):
        with pytest.raises(ValueError, match="bad shard spec"):
            read_shard(bad)


# ---------------------------------------------------------------------
# CLI end-to-end (the acceptance criterion)
# ---------------------------------------------------------------------

def _body(sam_path):
    with open(sam_path) as f:
        lines = [ln.rstrip("\n") for ln in f]
    header = [ln for ln in lines if ln.startswith("@")]
    return header, [ln for ln in lines if not ln.startswith("@")]


@pytest.fixture(scope="module")
def pe_sam(world, indexed):
    """One `cli mem` PE run over the on-disk world -> (header, body)."""
    out = str(world["dir"] / "out_pe.sam")
    assert cli.main(["mem", world["fa"], world["fq1"], world["fq2"],
                     "-o", out]) == 0
    return _body(out)


def test_cli_mem_pe_byte_identical(world, indexed, pe_sam):
    """`cli index` + `cli mem` on the gzipped 3-contig FASTA + gzipped
    paired FASTQ == align_pairs_optimized in memory on the same data,
    with the index coming from the load_index round-trip."""
    header, body = pe_sam
    want, _ = align_pairs_optimized(
        indexed, world["r1"], world["r2"],
        names=[f"pair{i}" for i in range(N_PAIRS)])
    assert body == want
    assert header[:4] == sam_header(indexed)
    assert header[4].startswith("@PG\tID:repro\t")
    # sanity: output actually exercises the multi-contig machinery
    assert len({ln.split("\t")[2] for ln in body} - {"*"}) == 3


def test_cli_mem_se_byte_identical(world, indexed):
    out = str(world["dir"] / "out_se.sam")
    assert cli.main(["mem", world["fa"], world["fq1"], "-o", out]) == 0
    _, body = _body(out)
    results, _ = align_reads_optimized(indexed, world["r1"])
    want = to_sam(world["r1"], results,
                  names=[f"pair{i}/1" for i in range(N_PAIRS)], idx=indexed)
    assert body == want


def test_cli_mem_interleaved_and_shard(world, indexed, pe_sam):
    """Interleaved ingestion and --shard i/n both reproduce slices of the
    split-file run."""
    il = str(world["dir"] / "il.fq.gz")
    recs = []
    for a, b in zip(read_fastq(world["fq1"]), read_fastq(world["fq2"])):
        recs.extend([a, b])
    iofastq.write_fastq(il, recs)
    out_il = str(world["dir"] / "out_il.sam")
    assert cli.main(["mem", "-p", world["fa"], il, "-o", out_il]) == 0
    assert _body(out_il)[1] == pe_sam[1]

    out_sh = str(world["dir"] / "out_sh.sam")
    assert cli.main(["mem", world["fa"], world["fq1"], world["fq2"],
                     "--shard", "1/4", "-o", out_sh]) == 0
    _, body_sh = _body(out_sh)
    qnames = [ln.split("\t")[0] for ln in body_sh]
    assert qnames == [f"pair{k}" for k in range(1, N_PAIRS, 4)
                      for _ in (0, 1)]
    # sharded batch != full batch for PE stats, so only QNAMEs are compared


def test_cli_mem_builds_in_memory_without_bundle(world, tmp_path, pe_sam):
    """`mem` on a FASTA with no bundle falls back to an in-memory build
    and still emits the same records (fresh build == loaded bundle)."""
    fa2 = str(tmp_path / "ref2.fa.gz")
    write_fasta(fa2, world["contigs"])
    assert not have_index(fa2)
    out = str(tmp_path / "out.sam")
    assert cli.main(["mem", fa2, world["fq1"], world["fq2"],
                     "-o", out]) == 0
    assert _body(out)[1] == pe_sam[1]
