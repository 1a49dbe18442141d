"""Pure-jnp oracle for the BSW Pallas kernel.

The ultimate spec is the scalar ``repro.core.bsw.bsw_extend`` (the
ksw_extend2 port); this reference exposes it with the kernel's padded
array interface so shape sweeps can assert exact equality.
"""

from __future__ import annotations

import numpy as np

from repro.core.bsw import BSWParams, bsw_extend


def bsw_ref(qs: np.ndarray, ts: np.ndarray, qlens, tlens, h0s, ws,
            p: BSWParams) -> np.ndarray:
    """Same interface as bsw_pallas_call, computed by the scalar oracle."""
    W = qs.shape[0]
    out = np.zeros((W, 6), np.int32)
    for i in range(W):
        r = bsw_extend(np.asarray(qs[i, :qlens[i]], np.uint8),
                       np.asarray(ts[i, :tlens[i]], np.uint8),
                       int(h0s[i]), p, int(ws[i]))
        out[i] = (r.score, r.qle, r.tle, r.gtle, r.gscore, r.max_off)
    return out
