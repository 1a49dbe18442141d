"""jit'd wrappers: Pallas-backed occ and full backward extension.

The public entry points (``occ_pallas`` / ``backward_ext_pallas``) are
plain Python wrappers that resolve ``interpret`` and call the jitted
implementations; the kernel tests drive them.  The pipeline does not:
the SMEM search jits its own device loops around ``make_occ_fn``'s
callable, and their ``kernel.fmocc`` span and dispatch count live there
(``core.smem._run_loop``).

``interpret`` resolves from the active JAX backend when left ``None``
(interpret on CPU, compiled on TPU/GPU — see ``kernels.config``).

``make_occ_fn`` builds the pipeline-facing occ callable for one
(layout, qb, interpret) configuration.  The SMEM search passes occ
functions as STATIC jit arguments (``core.smem._smem1_loop_j``), so the
factory is cached: one stable function object per configuration, no
retraces across calls or indexes.  The engine's occ-layout sweep
(``kernels.engine``) times these configurations and picks one per
index + backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.fmindex import FMArrays, I32
from ..config import resolve_interpret
from .kernel import (occ_count_pallas_call, occ_count_packed_pallas_call,
                     QB)

#: occ-bucket layouts the kernels implement: eta=32 (paper-optimized,
#: one byte/base) and eta=128 (original bwa-mem, 2-bit packed)
LAYOUTS = ("eta32", "eta128")


def _occ_impl(fm: FMArrays, c: jnp.ndarray, i: jnp.ndarray, *,
              layout: str = "eta32", qb: int = QB,
              interpret: bool = True) -> jnp.ndarray:
    """Occ(c, i) over flat query vectors via the Pallas compare+count kernel.

    XLA performs the bucket gather (one vectorized load per lockstep round
    — the batching-as-prefetch adaptation); Pallas does the byte-compare +
    popcount over the gathered 32-byte rows.  ``layout`` picks the bucket
    encoding; for eta=128 the sentinel correction (primary row packed as
    code 0, see ``fmindex.occ_base_v``) is folded into the additive base
    so the kernel body stays a pure compare+count.
    """
    shape = c.shape
    cf = c.reshape(-1).astype(I32)
    i_f = i.reshape(-1).astype(I32)
    p = i_f + 1
    if layout == "eta32":
        b = p >> 5
        r = p & 31
        base = fm.occ32_counts[b, cf]
        rows = fm.occ32_bytes[b]
        call = occ_count_pallas_call
    elif layout == "eta128":
        b = p >> 7
        r = p & 127
        corr = ((cf == 0) & (fm.primary >= (b << 7)) &
                (fm.primary < p)).astype(I32)
        base = fm.occ128_counts[b, cf] - corr
        rows = fm.occ128_packed[b]
        call = occ_count_packed_pallas_call
    else:
        raise ValueError(f"unknown occ layout {layout!r} "
                         f"(known: {', '.join(LAYOUTS)})")
    T = cf.shape[0]
    Tp = -(-T // qb) * qb
    pad = Tp - T
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    column = lambda v: jnp.pad(v, (0, pad)).reshape(Tp, 1)
    out = call(rows, column(cf), column(r), column(base), qb=qb,
               interpret=interpret)
    return out[:T, 0].reshape(shape)


_occ_pallas_jit = jax.jit(_occ_impl,
                          static_argnames=("layout", "qb", "interpret"))


@functools.lru_cache(maxsize=None)
def _make_occ_fn(layout: str, qb: int, interpret: bool):
    def occ(fm: FMArrays, c: jnp.ndarray, i: jnp.ndarray) -> jnp.ndarray:
        return _occ_impl(fm, c, i, layout=layout, qb=qb, interpret=interpret)
    occ.__name__ = occ.__qualname__ = f"occ_pallas_{layout}_qb{qb}"
    occ.is_pallas = True
    occ.layout = layout
    occ.qb = qb
    occ.interpret = interpret
    return occ


def make_occ_fn(layout: str = "eta32", qb: int = QB,
                interpret: bool | None = None):
    """One STABLE occ callable per (layout, qb, interpret) configuration.

    The returned function has the ``occ_fn(fm, c, i)`` signature of
    ``fmindex.occ_opt_v`` (traceable inside jit) and carries
    ``is_pallas`` / ``layout`` / ``qb`` / ``interpret`` attributes so the
    SMEM dispatcher can recognise and instrument it.  Cached so repeated
    calls return the SAME object — safe as a static jit argument.
    """
    return _make_occ_fn(layout, int(qb), resolve_interpret(interpret))


def _backward_ext_impl(fm: FMArrays, k, l, s, c, *, interpret: bool = True):
    """Full bi-interval backward extension with Pallas occ (kernel analogue
    of core.fmindex.backward_ext_v)."""
    k = k.astype(I32); l = l.astype(I32); s = s.astype(I32)
    cc = jnp.clip(c, 0, 3).astype(I32)
    batch = k.shape
    c4 = jnp.broadcast_to(jnp.arange(4, dtype=I32), batch + (4,))
    i1 = jnp.broadcast_to((k - 1)[..., None], batch + (4,))
    i2 = jnp.broadcast_to((k + s - 1)[..., None], batch + (4,))
    o1 = _occ_impl(fm, c4, i1, interpret=interpret)
    o2 = _occ_impl(fm, c4, i2, interpret=interpret)
    ks = fm.C + o1
    ss = o2 - o1
    sent = ((k <= fm.primary) & (fm.primary < k + s)).astype(I32)
    l3 = l + sent
    l2 = l3 + ss[..., 3]
    l1 = l2 + ss[..., 2]
    l0 = l1 + ss[..., 1]
    ls = jnp.stack([l0, l1, l2, l3], axis=-1)
    take = lambda a_: jnp.take_along_axis(a_, cc[..., None], axis=-1)[..., 0]
    s_out = jnp.where(c > 3, 0, take(ss))
    return take(ks), take(ls), s_out


_backward_ext_pallas_jit = jax.jit(_backward_ext_impl,
                                   static_argnames=("interpret",))


def occ_pallas(fm: FMArrays, c: jnp.ndarray, i: jnp.ndarray, *,
               layout: str = "eta32", qb: int = QB,
               interpret: bool | None = None) -> jnp.ndarray:
    """Public Occ(c, i) entry point (see module docstring)."""
    return _occ_pallas_jit(fm, c, i, layout=layout, qb=qb,
                           interpret=resolve_interpret(interpret))


def backward_ext_pallas(fm: FMArrays, k, l, s, c, *,
                        interpret: bool | None = None):
    """Public backward-extension entry point (see module docstring)."""
    return _backward_ext_pallas_jit(fm, k, l, s, c,
                                    interpret=resolve_interpret(interpret))
