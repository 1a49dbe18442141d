"""jit'd public wrapper for the BSW Pallas kernel."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.core.bsw import BSWParams, ExtResult, adjusted_band
from ..config import resolve_interpret
from .kernel import bsw_pallas_call, LANES


def bsw_extend_pallas(queries, targets, h0s, p: BSWParams, ws=None,
                      qmax: int | None = None, tmax: int | None = None,
                      interpret: bool | None = None):
    """Drop-in equivalent of ``core.bsw.bsw_extend_batch`` that runs the
    Pallas kernel.

    Accepts the same ``qmax``/``tmax`` padded-shape hints as the jnp
    batch so ``bsw_extend_tasks`` can use it as a ``batch_fn`` — padding
    to the caller's rounded shape keeps the number of distinct
    (qmax, tmax) jit signatures (and hence kernel recompiles) bounded.
    ``interpret=None`` resolves from the active backend: interpret on
    CPU, compiled on TPU/GPU (kernels.config).
    """
    itp = resolve_interpret(interpret)
    with obs.span("kernel.bsw_pallas", cat="kernel", lanes=len(queries)):
        obs.count("kernel_bsw_dispatches")
        return _bsw_extend_pallas(queries, targets, h0s, p, ws,
                                  qmax, tmax, itp)


def _bsw_extend_pallas(queries, targets, h0s, p, ws, qmax, tmax, interpret):
    W = len(queries)
    qlens = np.array([len(q) for q in queries], np.int32)
    tlens = np.array([len(t) for t in targets], np.int32)
    if qmax is None:
        qmax = max(int(qlens.max()), 1)
    if tmax is None:
        tmax = max(int(tlens.max()), 1)
    Wp = -(-W // LANES) * LANES
    qs = np.full((Wp, qmax), 4, np.int32)
    ts = np.full((Wp, tmax), 4, np.int32)
    for i, (q, t) in enumerate(zip(queries, targets)):
        qs[i, :len(q)] = q
        ts[i, :len(t)] = t
    ws_in = np.ones(Wp, np.int32)
    h0_in = np.ones(Wp, np.int32)
    ql_in = np.ones(Wp, np.int32)
    tl_in = np.ones(Wp, np.int32)
    ql_in[:W] = qlens
    tl_in[:W] = tlens
    h0_in[:W] = np.asarray(h0s, np.int32)
    for i in range(W):
        ws_in[i] = adjusted_band(int(qlens[i]), p,
                                 p.w if ws is None else int(ws[i]))
    col = lambda v: jnp.asarray(v[:, None])
    out = bsw_pallas_call(
        jnp.asarray(qs), jnp.asarray(ts), col(ql_in), col(tl_in),
        col(h0_in), col(ws_in),
        a=p.a, b=p.b, o_del=p.o_del, e_del=p.e_del, o_ins=p.o_ins,
        e_ins=p.e_ins, zdrop=p.zdrop, qmax=qmax, tmax=tmax,
        interpret=interpret)
    out = np.asarray(out)
    return [ExtResult(*(int(v) for v in out[i])) for i in range(W)]
