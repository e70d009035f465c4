"""Reduction of a JAX profiler trace to device busy time, kernel time
and idle gaps labelled by what the program was doing.

``read_xplane`` turns an ``.xplane.pb`` file into plain lists: the
device operations of every TPU plane (the "XLA Ops" line) and the
host's ``TraceAnnotation`` events, both in nanoseconds on the trace's
clock.  A host annotation named ``bench.sync`` recorded at a known
``time.perf_counter()`` reading puts the trace on the program's span
clock.  The rest works on those lists alone, so it is tested on small
hand-made traces.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SYNC = "bench.sync"
# what the host was doing, innermost first
GAP_LABELS = ("kernel.launch", "device.upload", "train", "merge", "fetch",
              "plan", "serve.execute", "queue.wait")


@dataclass
class Op:
    name: str
    t0: float                  # seconds on the perf_counter clock
    t1: float
    device: int = 0


@dataclass
class Reduced:
    window: Tuple[float, float]
    ops: List[Op]
    n_devices: int
    busy_s: float              # mean over devices of the busy union
    gaps: List[Tuple[float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def read_xplane(path: str) -> Tuple[List[Tuple], List[Tuple]]:
    """(device ops, host annotations) of one trace file.

    Device ops are (name, start_ns, dur_ns, device_index); host events
    are (name, start_ns, dur_ns)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev, host = [], []
    tpu = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" \
                not in plane.name:
            idx = tpu
            tpu += 1
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    dev.append((e.name, e.start_ns, e.duration_ns, idx))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns, e.duration_ns))
    return dev, host


def clock_offset(host_events: Iterable[Tuple], sync_pc: float) -> float:
    """Seconds to add to trace nanoseconds * 1e-9 to get perf_counter."""
    for name, start_ns, dur_ns in host_events:
        if name == SYNC:
            return sync_pc - (start_ns + dur_ns / 2) * 1e-9
    raise ValueError(f"no {SYNC} annotation in the trace")


def union_length(intervals: Sequence[Tuple[float, float]],
                 lo: float, hi: float) -> Tuple[float, List[Tuple]]:
    """Length of the union of intervals clipped to [lo, hi], and the
    idle gaps between them."""
    busy, gaps, cur = 0.0, [], lo
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if a > cur:
            gaps.append((cur, a))
        if b > cur:
            busy += b - max(a, cur)
            cur = b
    if cur < hi:
        gaps.append((cur, hi))
    return busy, gaps


def short_name(name: str) -> str:
    """An HLO op's own name: "%fusion.84 = f32[...] fusion(...)" gives
    "fusion.84"."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


# control flow encloses the ops of its body on the same line
_ENCLOSING = ("while", "conditional", "call")


def reduce(dev_ops: Sequence[Tuple], offset: float,
           window: Tuple[float, float]) -> Reduced:
    ops = [Op(short_name(n), s * 1e-9 + offset, (s + d) * 1e-9 + offset, i)
           for n, s, d, i in dev_ops]
    ops = [o for o in ops if o.t1 > window[0] and o.t0 < window[1]]
    devices = sorted({o.device for o in ops}) or [0]
    busy_total, gaps = 0.0, []
    for d in devices:
        b, g = union_length([(o.t0, o.t1) for o in ops if o.device == d],
                            *window)
        busy_total += b
        if d == devices[0]:
            gaps = g
    return Reduced(window=window, ops=ops, n_devices=len(devices),
                   busy_s=busy_total / len(devices), gaps=gaps)


def matches(op: Op, patterns: Sequence[str]) -> bool:
    """An op is a kernel's when its name starts with one of the kernel's
    names (the named scope the wrapper launches it under)."""
    return op.name.startswith(tuple(patterns))


def kernel_seconds(red: Reduced, patterns: Sequence[str],
                   within: Optional[Sequence[Tuple[float, float]]] = None
                   ) -> float:
    """Device seconds of the ops that match, clipped to the window; with
    ``within``, only ops whose midpoint lies in one of those intervals."""
    total = 0.0
    for o in red.ops:
        if not matches(o, patterns):
            continue
        if within is not None:
            mid = (o.t0 + o.t1) / 2
            if not any(a <= mid <= b for a, b in within):
                continue
        total += min(o.t1, red.window[1]) - max(o.t0, red.window[0])
    return total


def top_ops(red: Reduced, n: int = 10) -> List[List]:
    """Device seconds by op name (numbered instances of one op summed),
    leaving out control flow, which encloses other ops."""
    by: Dict[str, float] = {}
    for o in red.ops:
        base = o.name.rsplit(".", 1)[0] if o.name.rsplit(".", 1)[-1] \
            .isdigit() else o.name
        if base.split(".")[0] in _ENCLOSING:
            continue
        by[base] = by.get(base, 0.0) + (
            min(o.t1, red.window[1]) - max(o.t0, red.window[0]))
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def label_gaps(gaps: Sequence[Tuple[float, float]],
               spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle seconds by the innermost labelled program span that covers
    each gap's midpoint ("none" where no query was in flight)."""
    rank = {name: i for i, name in enumerate(GAP_LABELS)}
    ranked = [(rank[n], n, a, b) for n, a, b in spans if n in rank]
    out: Dict[str, float] = {}
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2
        best = min(((r, n) for r, n, a, b in ranked if a <= mid <= b),
                   default=(len(rank), "none"))
        out[best[1]] = out.get(best[1], 0.0) + (g1 - g0)
    return out


def top_gaps(labelled: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in
            sorted(labelled.items(), key=lambda kv: -kv[1])[:n]]
