"""The reduction from a profiler trace to device metrics.

Input is what `benchmark/launcher.py` writes beside the .xplane.pb: the
device planes' events as [name, start_ns, duration_ns] on the trace's own
timeline, and the start of the `bench_anchor` host event on that timeline.
The launcher recorded the wall clock inside that annotation, so
wall = trace + (anchor_wall_ns - anchor_ns), and the parent's own
send/answer times (wall clock, same machine) land on the same axis.

On a TPU v5e (jax 0.9.0) a device plane is `/device:TPU:<n>` with the
lines `XLA Modules` (one event per executed jit program), `XLA Ops` (its
instructions) and `Async XLA Ops` (copies in flight). Busy time is the
union of the two op lines: the seconds in which an operation ran.
"""

from __future__ import annotations

from bisect import bisect_left, bisect_right

OP_LINES = ("XLA Ops", "Async XLA Ops")


def merge(intervals) -> list:
    """Sorted, disjoint [start, end) covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        elif b > a:
            out.append([a, b])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def covered(merged, lo, hi) -> int:
    """ns of [lo, hi) that the disjoint sorted intervals cover."""
    starts = [a for a, _ in merged]
    i = max(bisect_right(starts, lo) - 1, 0)
    total = 0
    while i < len(merged) and merged[i][0] < hi:
        total += max(0, min(merged[i][1], hi) - max(merged[i][0], lo))
        i += 1
    return total


class DeviceTrace:
    """One traced window. `spans`: [(label, wall start ns, wall end ns)],
    what the host had in flight (statements, or one span for a write
    window); they label the idle gaps."""

    def __init__(self, events: dict, anchor_wall_ns: int,
                 window_wall_ns: tuple, spans=()):
        if events.get("anchor_ns") is None:
            raise ValueError("the trace holds no bench_anchor event")
        self.offset = anchor_wall_ns - events["anchor_ns"]
        self.lo = window_wall_ns[0] - self.offset
        self.hi = window_wall_ns[1] - self.offset
        self.spans = sorted((a - self.offset, b - self.offset, label)
                            for label, a, b in spans)
        self.planes = {}        # plane -> merged busy intervals in window
        self.ops = {}           # op name -> ns inside the window, all planes
        for plane, lines in sorted(events["planes"].items()):
            intervals = []
            for line in OP_LINES:
                for name, start, dur in lines.get(line, ()):
                    a, b = max(start, self.lo), min(start + dur, self.hi)
                    if b > a:
                        intervals.append((a, b))
                        self.ops[name] = self.ops.get(name, 0) + (b - a)
            self.planes[plane] = merge(intervals)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the device
        planes (the chips used)."""
        if not self.planes:
            return 0.0
        return sum(sum(b - a for a, b in m)
                   for m in self.planes.values()) / len(self.planes) / 1e9

    def busy_ns_between(self, wall_lo: int, wall_hi: int) -> float:
        """Device time inside one host span (wall clock), averaged over
        the planes: a statement's kernel time."""
        if not self.planes:
            return 0.0
        lo = max(wall_lo - self.offset, self.lo)
        hi = min(wall_hi - self.offset, self.hi)
        return sum(covered(m, lo, hi)
                   for m in self.planes.values()) / len(self.planes)

    def device_ops(self, top: int = 10) -> list:
        ranked = sorted(self.ops.items(), key=lambda kv: (-kv[1], kv[0]))
        return [[name, ns / 1e9] for name, ns in ranked[:top]]

    def idle_gaps(self, top: int = 10) -> list:
        """Idle seconds of the first device plane by what the host was
        doing: `<label>:before_first_device_op`, `:between_device_ops`,
        `:after_last_device_op` inside a span, `between_statements`
        outside all of them (a gap is cut at span borders)."""
        if not self.planes:
            return [["no_device_plane", self.window_s]]
        busy = next(iter(self.planes.values()))
        gaps, at = [], self.lo
        for a, b in busy:
            if a > at:
                gaps.append((at, a))
            at = b
        if self.hi > at:
            gaps.append((at, self.hi))
        busy_starts = [a for a, _ in busy]
        totals = {}
        for glo, ghi in gaps:
            at = glo
            for slo, shi, label in self.spans:
                if shi <= at or slo >= ghi:
                    continue
                if slo > at:
                    totals["between_statements"] = totals.get(
                        "between_statements", 0) + (slo - at)
                    at = slo
                end = min(shi, ghi)
                # is any device work of this span before / after the gap?
                i = bisect_left(busy_starts, slo)
                before = i < len(busy) and busy[i][0] < at
                j = bisect_left(busy_starts, end)
                after = j < len(busy) and busy[j][0] < shi
                kind = ("between_device_ops" if before and after else
                        "after_last_device_op" if before else
                        "before_first_device_op" if after else
                        "no_device_op")
                key = f"{label}:{kind}"
                totals[key] = totals.get(key, 0) + (end - at)
                at = end
                if at >= ghi:
                    break
            if at < ghi:
                totals["between_statements"] = totals.get(
                    "between_statements", 0) + (ghi - at)
        ranked = sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))
        return [[name, ns / 1e9] for name, ns in ranked[:top]]
