"""Operations and bytes of the program's kernels, counted from what the
program reports and never from padded block sizes.

fmocc (``repro.kernels.fmocc``): one SMEM extension round asks Occ(c, k-1)
and Occ(c, k+s-1) for the four bases of each live interval, 8 queries per
task.  Each query has to read at least one 32-bit count, whatever the
occ layout, so 4 bytes per query is a floor on the bytes any layout
moves: a share of the HBM roofline taken from it cannot pass 100%.  The
program's ``kernel.fmocc`` span carries the live task count of each
dispatch (``tasks``); a backward round serves every live entry of a task,
and counting one per task keeps the floor.

BSW (``repro.kernels.bsw``): the program counts the cells of the
Smith-Waterman matrices that its extension and mate-rescue tasks needed
(``cells_useful``, ``rescue_cells_useful``) apart from the padded cells it
computed.  Useful cells over the kernel's device time is a rate in cell
updates per second; v5e publishes no int32 VPU peak to take a share of.
The time is that of the kernel's own operation (``BSW_KERNEL_OP``), as
the body ``_bsw_kernel_body`` runs inside it.
"""

from __future__ import annotations

FMOCC_QUERIES_PER_TASK = 8
FMOCC_BYTES_PER_QUERY = 4


def fmocc_floor_bytes(host_spans) -> int:
    """Bytes the fmocc dispatches of ``host_spans`` read, at least."""
    tasks = sum(int(args.get("tasks", 0)) for name, _, _, args in host_spans
                if name == "kernel.fmocc")
    return tasks * FMOCC_QUERIES_PER_TASK * FMOCC_BYTES_PER_QUERY


def bsw_useful_cells(snapshot: dict) -> int:
    return int(snapshot.get("cells_useful", 0)
               + snapshot.get("rescue_cells_useful", 0))


def _spans(host_spans, name: str) -> list:
    return [(s, e) for n, s, e, _ in host_spans if n == name]


def fmocc_device_s(device, host_spans) -> float:
    """Device time of the occ step: every device operation that ran inside
    a ``kernel.fmocc`` span.  With the program's telemetry on, each SMEM
    round's dispatch waits for its result inside that span, so this is the
    round program's whole device time: the XLA gather of the occ rows, the
    Pallas compare+count kernel and the interval arithmetic around it.
    Fusing the gather into the kernel moves no time out of it."""
    return device.time_within(_spans(host_spans, "kernel.fmocc"))


#: the Pallas BSW kernel's operation in the device trace: XLA names the
#: custom call after the jitted ``bsw_pallas_call`` that holds it, and the
#: layout copies around it in the same program are left out
BSW_KERNEL_OP = "bsw_pallas_call"


def bsw_device_s(device, host_spans) -> float:
    """Device time of the Pallas BSW kernel alone (``BSW_KERNEL_OP``)
    inside the program's ``kernel.bsw_pallas`` dispatch spans."""
    return device.time_within(_spans(host_spans, "kernel.bsw_pallas"),
                              BSW_KERNEL_OP)
