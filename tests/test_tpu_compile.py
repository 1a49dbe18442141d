"""Ahead-of-time compiles of the device path for a described TPU v5e.

Interpret mode (every other kernel test) cannot show what the chip's
compiler refuses: layouts, loop-carried vector types, block shapes.
These tests lower and compile the Pallas kernels and the SMEM device loops
that call them for a ``v5e:2x2`` topology that is described, not attached,
at the widths the aligner runs: BSW at 2x150 read widths and at
qmax=512, both occ layouts at the sweep's qb values, and the smem1 and
seed_strategy1 loops on the shapes of a GRCh38 chr21-sized FM-index.

Nothing runs, so no result or time is checked here; ``chip_smoke.py``
runs the same path on the chip.  The topology is described inside a
module fixture (never at import) because only one process may load the
TPU library at a time.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fmindex import BASE_ETA, OPT_ETA, FMArrays
from repro.core.smem import _seed1_loop_j, _smem1_loop_j
from repro.kernels.bsw.kernel import bsw_pallas_call
from repro.kernels.fmocc.kernel import (occ_count_packed_pallas_call,
                                        occ_count_pallas_call)
from repro.kernels.fmocc.ops import make_occ_fn

CHR21_BP = 46_709_983          # GRCh38 chr21
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache entirely
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype=I32: jax.ShapeDtypeStruct(dims, dtype,
                                                        sharding=one_chip)


@pytest.mark.parametrize("qmax,tmax", [(160, 192), (512, 544)])
def test_bsw_kernel_compiles(shape, qmax, tmax):
    W = 256
    col = shape((W, 1))
    compiled = bsw_pallas_call.lower(
        shape((W, qmax)), shape((W, tmax)), col, col, col, col,
        a=1, b=4, o_del=6, e_del=1, o_ins=6, e_ins=1, zdrop=100,
        qmax=qmax, tmax=tmax, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("qb", [256, 512])
@pytest.mark.parametrize("call", [occ_count_pallas_call,
                                  occ_count_packed_pallas_call],
                         ids=["eta32", "eta128"])
def test_fmocc_kernel_compiles(shape, call, qb):
    T = 8 * qb
    col = shape((T, 1))
    compiled = call.lower(shape((T, 32), jnp.uint8), col, col, col,
                          qb=qb, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _chr21_fmarrays(shape) -> FMArrays:
    N = 2 * CHR21_BP + 1
    nb32, nb128 = N // OPT_ETA + 1, N // BASE_ETA + 1
    return FMArrays(
        occ32_counts=shape((nb32, 4)),
        occ32_bytes=shape((nb32, OPT_ETA), jnp.uint8),
        occ128_counts=shape((nb128, 4)),
        occ128_packed=shape((nb128, 32), jnp.uint8),
        C=shape((4,)), primary=shape(()),
        sa=shape((N,)), sa_sampled=shape((-(-N // 32),)),
        bwt=shape((N,), jnp.uint8), n_ref=shape(()), N=shape(()))


@pytest.mark.parametrize("layout", ["eta32", "eta128"])
def test_smem_round_with_compiled_occ_kernel(shape, layout):
    """The smem1 loop over 1024 tasks of 150 bp reads (151 SMEM slots per
    task), both phases' rounds in one program, on chr21-sized index
    shapes, with the occ lookups in the compiled Pallas kernel."""
    T, L = 1024, 150
    occ_fn = make_occ_fn(layout, 256, interpret=False)
    col = shape((T,))
    compiled = _smem1_loop_j.lower(
        _chr21_fmarrays(shape), shape((T, L), jnp.uint8), col, col, col,
        occ_fn=occ_fn, P=L + 1).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_seed_strategy1_loop_with_compiled_occ_kernel(shape):
    """The third seeding round's loop, as the smem1 loop above."""
    T, L = 1024, 150
    occ_fn = make_occ_fn("eta32", 256, interpret=False)
    col = shape((T,))
    compiled = _seed1_loop_j.lower(
        _chr21_fmarrays(shape), shape((T, L), jnp.uint8), col, col,
        shape(()), shape(()), occ_fn=occ_fn).compile()
    assert "tpu_custom_call" in compiled.as_text()
