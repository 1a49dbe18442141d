"""The "pallas" engine: golden byte-identity with "baseline", kernel
edge cases the golden suites don't hit, the occ-layout sweep, and the
interpret-mode resolution (kernels.config).

Worlds are kept deliberately small: every pipeline run here executes the
Pallas kernel bodies in interpret mode (CPU), which is orders of
magnitude slower per cell than the jnp lockstep path.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.api import Aligner, engines, get_engine
from repro.core import fmindex as fmx
from repro.core.bsw import BSWParams, adjusted_band, bsw_extend
from repro.core.contig import build_contig_index
from repro.data import (make_reference, simulate_pairs,
                        simulate_pairs_multi, simulate_reads,
                        simulate_reference)
from repro.kernels import config as kcfg
from repro.kernels.bsw import bsw_extend_pallas
from repro.kernels.engine import (DEFAULT_CANDIDATE, OccConfig,
                                  attach_occ_config)
from repro.kernels.fmocc import make_occ_fn, occ_pallas
from repro.options import AlignOptions


@pytest.fixture(scope="module")
def world():
    ref = make_reference(12000, seed=7)
    idx = fmx.build_index(ref)
    reads, _ = simulate_reads(ref, 8, 101, seed=3)
    return idx, reads


@pytest.fixture(scope="module")
def pe_world():
    ref = make_reference(20000, seed=5)
    idx = fmx.build_index(ref)
    r1, r2, _ = simulate_pairs(ref, 12, 101, insert_mean=300, insert_std=30,
                               seed=9, burst_frac=0.25)
    return idx, r1, r2


@pytest.fixture(scope="module")
def contig_world():
    contigs = simulate_reference(30000, 3, seed=11)
    idx = build_contig_index(contigs)
    r1, r2, _ = simulate_pairs_multi(contigs, 8, 101, seed=13,
                                     insert_mean=300, insert_std=30)
    return idx, r1, r2


# ---------------------------------------------------------------------
# Registry / options surface
# ---------------------------------------------------------------------

def test_engine_registered():
    assert "pallas" in engines()
    eng = get_engine("pallas")
    assert eng.se is not None and eng.pe is not None


def test_cli_exposes_engine(capsys):
    from repro.cli import build_parser
    with pytest.raises(SystemExit):
        build_parser().parse_args(["mem", "--help"])
    assert "pallas" in capsys.readouterr().out


def test_cli_kernel_interpret_flag():
    from repro.cli import build_parser, _options_from_args
    ap = build_parser()
    for spelling, want in (("auto", None), ("on", True), ("off", False)):
        args = ap.parse_args(["mem", "ref.fa", "r.fq", "--engine", "pallas",
                              "--kernel-interpret", spelling])
        opt = _options_from_args(args)
        assert opt.engine == "pallas"
        assert opt.kernel_interpret is want


# ---------------------------------------------------------------------
# Golden byte-identity vs "baseline" (telemetry off AND on)
# ---------------------------------------------------------------------

def test_se_golden_identity(world):
    idx, reads = world
    want = Aligner(idx, AlignOptions(engine="baseline")).align(reads).sam()
    got = Aligner(idx, AlignOptions(engine="pallas")).align(reads)
    assert got.sam() == want
    traced = Aligner(idx, AlignOptions(engine="pallas"),
                     telemetry=True).align(reads)
    assert traced.sam() == want
    # the Pallas kernels actually ran (both hot paths)
    assert traced.stats["kernel_bsw_dispatches"] > 0
    assert traced.stats["kernel_fmocc_dispatches"] > 0
    assert traced.stats["time_kernel.bsw_pallas_s"] > 0
    assert traced.stats["time_kernel.fmocc_s"] > 0


def test_pe_golden_identity(pe_world):
    idx, r1, r2 = pe_world
    want = Aligner(idx, AlignOptions(engine="baseline")).align_pairs(r1, r2)
    got = Aligner(idx, AlignOptions(engine="pallas"),
                  telemetry=True).align_pairs(r1, r2)
    assert got.sam() == want.sam()
    assert got.stats["kernel_bsw_dispatches"] > 0


def test_multicontig_golden_identity(contig_world):
    idx, r1, r2 = contig_world
    want = Aligner(idx, AlignOptions(engine="baseline")).align_pairs(r1, r2)
    got = Aligner(idx, AlignOptions(engine="pallas")).align_pairs(r1, r2)
    assert got.sam() == want.sam()
    assert len({r.rname for r in got.records()} - {"*"}) >= 2


def test_explicit_interpret_matches_auto(world):
    # on CPU, kernel_interpret=True and the auto default are the same mode
    idx, reads = world
    auto = Aligner(idx, AlignOptions(engine="pallas")).align(reads).sam()
    forced = Aligner(idx, AlignOptions(engine="pallas",
                                       kernel_interpret=True)).align(reads)
    assert forced.sam() == auto


# ---------------------------------------------------------------------
# Edge cases the golden suites don't hit
# ---------------------------------------------------------------------

def test_zero_length_and_all_n_reads(world):
    idx, reads = world
    L = reads.shape[1]
    batch = np.vstack([reads[:2],
                       np.full((1, L), 4, reads.dtype),    # all-N
                       reads[2:3]])
    lens = np.array([L, L, L, 0])                          # last: zero-length
    want = Aligner(idx, AlignOptions(engine="baseline")).align(
        batch, lens=lens)
    got = Aligner(idx, AlignOptions(engine="pallas")).align(batch, lens=lens)
    assert got.sam() == want.sam()
    recs = got.records()
    assert recs[-1].is_unmapped            # zero-length read
    assert any(r.qname == "read2" and r.is_unmapped for r in recs)  # all-N


@pytest.mark.parametrize("layout", ["eta32", "eta128"])
def test_occ_block_boundaries(world, layout):
    """occ at bucket edges and at i == len(bwt) - 1 (the full-BWT count:
    occ here is inclusive of position i, so N-1 covers the whole BWT)."""
    idx, _ = world
    N = int(idx.N)
    edges = [-1, 0, 30, 31, 32, 33, 126, 127, 128, 129, 255, 256,
             N - 130, N - 2, N - 1]
    ii = np.array([i for i in edges for _ in range(4)], np.int32)
    cc = np.array([c for _ in edges for c in range(4)], np.int32)
    got = occ_pallas(idx.device(), jnp.asarray(cc), jnp.asarray(ii),
                     layout=layout)
    want = fmx.occ_opt_v(idx.device(), jnp.asarray(cc), jnp.asarray(ii))
    assert (np.asarray(got) == np.asarray(want)).all()
    # full-BWT counts (i = N-1) sum to N-1: every row but the sentinel
    # holds one base 0..3, and both layouts' sentinel handling (skip vs
    # packed-as-0 + correction) must agree on that
    full = occ_pallas(idx.device(), jnp.arange(4, dtype=jnp.int32),
                      jnp.full(4, N - 1, jnp.int32), layout=layout)
    assert int(np.asarray(full).sum()) == N - 1


@pytest.mark.parametrize("qb", [64, 512])
def test_occ_qb_sweep_values_identical(world, qb):
    idx, _ = world
    rng = np.random.default_rng(qb)
    cc = jnp.asarray(rng.integers(0, 4, 300).astype(np.int32))
    ii = jnp.asarray(rng.integers(-1, idx.N, 300).astype(np.int32))
    got = occ_pallas(idx.device(), cc, ii, qb=qb)
    want = fmx.occ_opt_v(idx.device(), cc, ii)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_bsw_band_width_one():
    """ws=1 collapses the band to width 1 (adjusted_band floors at 1)."""
    p = BSWParams()
    assert adjusted_band(30, p, 1) == 1
    rng = np.random.default_rng(42)
    qs, ts, h0s = [], [], []
    for _ in range(12):
        ql = int(rng.integers(1, 40))
        tl = int(rng.integers(1, 48))
        qs.append(rng.integers(0, 4, ql).astype(np.uint8))
        ts.append(rng.integers(0, 4, tl).astype(np.uint8))
        h0s.append(int(rng.integers(1, 50)))
    got = bsw_extend_pallas(qs, ts, h0s, p, ws=[1] * 12)
    want = [bsw_extend(q, t, h0, p, 1)
            for q, t, h0 in zip(qs, ts, h0s)]
    assert got == want


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 5))
def test_property_narrow_band_roundtrip(seed, w):
    """Random narrow-band tasks: Pallas kernel == scalar oracle."""
    rng = np.random.default_rng(seed)
    ql = int(rng.integers(1, 30))
    tl = int(rng.integers(1, 36))
    q = rng.integers(0, 5, ql).astype(np.uint8)
    t = rng.integers(0, 5, tl).astype(np.uint8)
    h0 = int(rng.integers(1, 40))
    got = bsw_extend_pallas([q], [t], [h0], BSWParams(), ws=[w])[0]
    assert got == bsw_extend(q, t, h0, BSWParams(),
                             adjusted_band(ql, BSWParams(), w))


# ---------------------------------------------------------------------
# Occ-layout sweep + interpret resolution
# ---------------------------------------------------------------------

def test_sweep_attaches_and_caches(world):
    idx, _ = world
    cfg = attach_occ_config(idx)
    assert isinstance(cfg, OccConfig)
    assert (cfg.layout, cfg.qb) in {(lo, qb) for lo, qb, _ in cfg.timings} \
        or cfg.timings == ()
    assert attach_occ_config(idx) is cfg          # cached on the index
    # the chosen config's occ_fn is the stable cached callable
    assert cfg.occ_fn is make_occ_fn(cfg.layout, cfg.qb, cfg.interpret)
    assert cfg.occ_fn.is_pallas


def test_sweep_env_escape(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_SWEEP", "0")
    idx = fmx.build_index(make_reference(2000, seed=3))
    cfg = attach_occ_config(idx)
    assert (cfg.layout, cfg.qb) == DEFAULT_CANDIDATE
    assert cfg.timings == ()


def test_interpret_resolution(monkeypatch):
    # CPU in this environment: auto-resolve must say "interpret"
    assert kcfg.default_interpret() is True
    assert kcfg.resolve_interpret(None) is True
    # simulate a compiled backend: auto flips off, forcing True warns once
    monkeypatch.setattr(kcfg, "_default", False)
    monkeypatch.setattr(kcfg, "_warned", False)
    assert kcfg.resolve_interpret(None) is False
    with pytest.warns(RuntimeWarning, match="interpret mode"):
        assert kcfg.resolve_interpret(True) is True
    # the warning fires once per process: a second force stays silent
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert kcfg.resolve_interpret(True) is True


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and no other
    directory is configured; otherwise the fixed <repo>/.jax_cache.  The
    tests' own switch-off (conftest) is respected."""
    import jax
    assert kcfg.enable_compile_cache() is None         # off in tests
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", None)
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / env_dir))
            assert kcfg.enable_compile_cache() == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir is None
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(kcfg.REPO_ROOT / ".jax_cache")
            assert kcfg.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


@pytest.mark.parametrize("engine,kernel_interpret,mode", [
    ("pallas", None, "interpret"), ("pallas", True, "interpret"),
    ("pallas", False, "compiled"), ("batched", None, None),
    ("baseline", False, None)])
def test_device_summary(engine, kernel_interpret, mode):
    import jax
    d = kcfg.device_summary(engine, kernel_interpret)
    want = {"platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}
    if mode is not None:
        want["kernel_mode"] = mode
    assert d == want
