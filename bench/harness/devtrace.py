"""Reduction of a profiler trace to the device numbers of one window.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes are named ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per operation the device ran, with a start and a
duration in nanoseconds on the same clock as the host's planes.  The
window is found by the annotation the harness writes at its start
(``bench.window``, on a host thread) and lasts the run's ``--seconds``.

* busy: the union of the operation intervals of each device inside the
  window, averaged over the devices;
* idle gaps: the stretches of the window in which device 0 ran nothing;
* kernel time: the summed durations of the operations that ran inside
  given host spans (a kernel's dispatches, each waited for).

On a TPU the events of ``XLA Ops`` are named by the whole HLO
instruction (``%fusion.5 = s32[12928]{0} fusion(...), ...``); ``op_name``
keeps the instruction's name (``fusion.5``).
"""

from __future__ import annotations

import bisect
import dataclasses

OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class DeviceTrace:
    window: tuple              # (start_ns, end_ns) on the profiler clock
    #: per device: sorted [(name, start_ns, end_ns)] clipped to the window
    ops: list

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        if not self.ops:
            return 0.0
        return sum(_union(dev) for dev in self.ops) / len(self.ops) / 1e9

    def gaps(self) -> list:
        """[(start_ns, end_ns)] of device 0's idle stretches in the window."""
        out, at = [], self.window[0]
        for _, s, e in self.ops[0] if self.ops else []:
            if s > at:
                out.append((at, s))
            at = max(at, e)
        if at < self.window[1]:
            out.append((at, self.window[1]))
        return out

    def time_within(self, intervals, prefix: str = "") -> float:
        """Summed device time (averaged over devices) of the ops whose
        midpoint lies inside one of ``intervals`` [(start_ns, end_ns)]
        and whose name starts with ``prefix``."""
        if not self.ops:
            return 0.0
        iv = sorted(intervals)
        starts = [s for s, _ in iv]
        tot = 0
        for dev in self.ops:
            for name, s, e in dev:
                if not name.startswith(prefix):
                    continue
                i = bisect.bisect_right(starts, (s + e) / 2) - 1
                if i >= 0 and (s + e) / 2 <= iv[i][1]:
                    tot += e - s
        return tot / len(self.ops) / 1e9

    def top_ops(self, k: int = 10) -> list:
        """[[name, seconds]] of the k op names with the most device time
        (device 0)."""
        acc: dict = {}
        for n, s, e in self.ops[0] if self.ops else []:
            acc[n] = acc.get(n, 0) + (e - s)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, d / 1e9] for n, d in top]


def _union(ops) -> int:
    tot, cur_s, cur_e = 0, None, None
    for _, s, e in ops:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def op_name(event_name: str) -> str:
    """The HLO instruction's name of a device op event."""
    if event_name.startswith("%") and " = " in event_name:
        return event_name[1:event_name.index(" = ")]
    return event_name


def reduce_planes(planes, mark: str, seconds: float) -> DeviceTrace:
    """``planes``: iterable of (plane name, [(line name, [(event name,
    start_ns, duration_ns)])]) — what ``load`` reads from a file, kept
    apart so that tests can hand in planes of their own."""
    planes = list(planes)
    start = None
    for pname, lines in planes:
        if pname.startswith("/device:"):
            continue
        for _, events in lines:
            for name, s, _ in events:
                if name == mark and (start is None or s < start):
                    start = s
    if start is None:
        raise ValueError(f"no {mark!r} annotation in the trace")
    end = start + seconds * 1e9
    ops = []
    for pname, lines in planes:
        if not (pname.startswith("/device:") and pname[8:].split(":")[0]
                in ("TPU", "GPU")) or "SparseCore" in pname:
            continue
        dev = []
        for lname, events in lines:
            if lname != OPS_LINE:
                continue
            for name, s, d in events:
                e = s + d
                if e > start and s < end:
                    dev.append((op_name(name), max(s, start), min(e, end)))
        dev.sort(key=lambda t: t[1])
        ops.append(dev)
    return DeviceTrace((start, end), ops)


def load(path, mark: str, seconds: float) -> DeviceTrace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = [(pl.name, [(ln.name, [(e.name, int(e.start_ns),
                                     int(e.duration_ns))
                                    for e in ln.events])
                         for ln in pl.lines])
              for pl in pd.planes]
    return reduce_planes(planes, mark, seconds)
