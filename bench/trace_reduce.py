"""Reduce one profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read.

The trace is read with ``jax.profiler.ProfileData``.  Device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
HLO operation, named by the operation's HLO text (``%name = shape op(...)``).
Loops (``while``) appear beside the operations they contain.  Host spans
(``jax.profiler.TraceAnnotation``) are events of ``/host:CPU``'s lines.

What is computed, over the traced window (the host span ``window``):

* busy time: the union of a device's operation intervals, and the idle
  share ``1 - busy / window``, averaged over the devices used;
* kernel events: every ``tpu_custom_call`` operation, with its HLO text so
  a metric can tell which kernel it is and read its shapes;
* collective time: operations that move data between chips, and the part
  of it during which no other operation runs on that device (exposed);
* idle gaps: the intervals in which no operation runs, each attributed to
  the innermost host span that covers its midpoint;
* the device operations that took most time (loops left out, since their
  bodies are counted).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")
CONTAINER = re.compile(r"(?<![\w-])(while|conditional|call)\(")
KERNEL = "tpu_custom_call"
SHAPE = re.compile(r"\b(bf16|f32|f16|s32|u32|f8e4m3fn|s8|pred)\[([0-9,]*)\]")
DTYPE_BYTES = {"bf16": 2, "f32": 4, "f16": 2, "s32": 4, "u32": 4,
               "f8e4m3fn": 1, "s8": 1, "pred": 1}


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class TraceSummary:
    window_ns: float  # the host span's length
    busy_window_ns: float  # from the first device operation to its end
    busy_ns: float  # averaged over devices
    n_devices: int
    kernels: List[Event]  # tpu_custom_call events inside the window
    collective_ns: float
    collective_exposed_ns: float
    top_ops: List[Tuple[str, float]]  # (label, seconds)
    idle_gaps: List[Tuple[str, float]]  # (host span, seconds) summed
    host_spans: Dict[str, List[float]]  # name -> durations (ns)
    longest_gaps: List[Tuple[float, float, str]]  # (offset s, s, host span)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.busy_window_ns


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def measure(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a_iv, b_iv) -> float:
    """Measure of the union of ``a_iv`` less the union of ``b_iv``."""
    a_iv, b_iv = union(a_iv), union(b_iv)
    total, j = 0.0, 0
    for a, b in a_iv:
        cur = a
        while j < len(b_iv) and b_iv[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_iv) and b_iv[k][0] < b:
            if b_iv[k][0] > cur:
                total += b_iv[k][0] - cur
            cur = max(cur, b_iv[k][1])
            k += 1
        if cur < b:
            total += b - cur
    return total


def op_label(text: str) -> str:
    """Short label of an HLO operation: its name, and the kernel marker or
    the output's first shape."""
    name = text.split(" = ", 1)[0]
    if KERNEL in text:
        shapes = SHAPE.findall(text.split(" = ", 1)[-1].split("custom-call(")[0])
        first = f"{shapes[0][0]}[{shapes[0][1]}]" if shapes else ""
        return f"{name} tpu_custom_call {first} x{len(shapes)}"
    m = SHAPE.search(text)
    return f"{name} {m.group(0)}" if m else name


def output_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, shape) of each output of an HLO operation's text."""
    rhs = text.split(" = ", 1)[-1]
    head = rhs.split("custom-call(")[0] if "custom-call(" in rhs else rhs
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in SHAPE.findall(head)]


def operand_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, shape) of each operand of a custom call's HLO text."""
    if "custom-call(" not in text:
        return []
    args = text.split("custom-call(", 1)[1].split("), ", 1)[0]
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in SHAPE.findall(args)]


def nbytes(shapes) -> int:
    total = 0
    for dt, shp in shapes:
        n = DTYPE_BYTES[dt]
        for s in shp:
            n *= s
        total += n
    return total


def reduce_trace(path: str, window: str = "bench.window",
                 attribute: Optional[List[str]] = None,
                 gap_min_ns: float = 20e3) -> TraceSummary:
    """Reduce the trace at ``path`` (a file or a directory holding one)
    over the host span named ``window``.  Idle gaps longer than
    ``gap_min_ns`` are attributed to the innermost covering host span
    whose name starts with one of ``attribute`` (default: any span that
    is not the window)."""
    import jax

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            if ops:
                devices.append(_events(ops[0]))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend(_events(ln))
    win = [e for e in host if e.name == window]
    if not win:
        raise ValueError(f"no host span {window!r} in {path}")
    lo, hi = win[0].start, win[-1].end
    window_ns = hi - lo
    devices = [d for d in devices if any(e.end > lo and e.start < hi
                                         for e in d)]
    if not devices:
        raise ValueError(f"no device operation in the window of {path}")
    # the profiler records device operations only some time after it
    # starts: busy and idle are taken from the first recorded operation
    first = min(e.start for d in devices for e in d if e.end > lo)
    lo_busy = max(lo, first)

    spans = [e for e in host if e.name != window and e.end > lo
             and e.start < hi and (attribute is None or any(
                 e.name.startswith(p) for p in attribute))]
    busy, kernels, coll, coll_exposed = [], [], 0.0, 0.0
    op_time: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    longest: List[Tuple[float, float, str]] = []
    for ops in devices:
        inside = [e for e in ops if e.end > lo and e.start < hi]
        iv = union(clip([(e.start, e.end) for e in inside], lo_busy, hi))
        busy.append(measure(iv))
        comm = [(e.start, e.end) for e in inside if COLLECTIVE.search(e.name)]
        compute = [(e.start, e.end) for e in inside
                   if not COLLECTIVE.search(e.name)
                   and not CONTAINER.search(e.name)]
        coll += measure(union(clip(comm, lo, hi)))
        coll_exposed += subtract(clip(comm, lo, hi), clip(compute, lo, hi))
        for e in inside:
            if KERNEL in e.name:
                kernels.append(e)
            if not CONTAINER.search(e.name):
                lab = op_label(e.name)
                op_time[lab] = op_time.get(lab, 0.0) + e.dur
        prev = lo_busy
        for a, b in iv + [(hi, hi)]:
            if a - prev >= gap_min_ns:
                mid = 0.5 * (a + prev)
                cover = [s for s in spans if s.start <= mid <= s.end]
                who = min(cover, key=lambda s: s.dur).name if cover \
                    else "no host span"
                gaps[who] = gaps.get(who, 0.0) + (a - prev)
                longest.append(((prev - lo) / 1e9, (a - prev) / 1e9, who))
            prev = max(prev, b)
    nd = len(devices)
    host_spans: Dict[str, List[float]] = {}
    for s in spans:
        if s.start >= lo and s.end <= hi:
            host_spans.setdefault(s.name, []).append(s.dur)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gap_list = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return TraceSummary(
        window_ns=window_ns,
        busy_window_ns=hi - lo_busy,
        busy_ns=sum(busy) / nd,
        n_devices=nd,
        kernels=kernels,
        collective_ns=coll / nd,
        collective_exposed_ns=coll_exposed / nd,
        top_ops=[(k, v / 1e9 / nd) for k, v in top],
        idle_gaps=[(k, v / 1e9 / nd) for k, v in gap_list],
        host_spans=host_spans,
        longest_gaps=sorted(longest, key=lambda g: -g[1])[:5],
    )
