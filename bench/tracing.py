"""Device trace capture, and its reduction to busy time, idle gaps and
time by operation.

The reduction works on plain event tuples ``(name, start_ns, end_ns)``,
so a test can check it on a small hand-made trace. ``load`` turns a JAX
profiler ``.xplane.pb`` into such tuples: the device ops of each chip
(the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane; an event's name
is its HLO text, ``%name = shape op(...)``) and the spans of the host's
Python thread (the ``python*`` lines of the ``/host:CPU`` plane, where
``jax.profiler.TraceAnnotation`` writes). Host and device clocks agree to
about half a millisecond.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that hold no other event. A ``while`` op spans its
    whole body, the gaps between the body's ops included, so only the
    ops with nothing nested in them say when the device works."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    holds = [False] * len(events)
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            holds[stack[-1]] = True
        stack.append(i)
    return [ev for ev, h in zip(events, holds) if not h]


def busy_ns(events: Sequence[Event]) -> float:
    """Length of the union of the leaf events' intervals."""
    return sum(e - s for s, e in union([(s, e) for _, s, e in leaves(events)]))


def gaps(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """The idle intervals between the first and the last leaf event,
    those inside an enclosing op included."""
    cover = union([(s, e) for _, s, e in leaves(events)])
    return [(a[1], b[0]) for a, b in zip(cover, cover[1:]) if b[0] > a[1]]


def op_name(text: str) -> str:
    """``%fusion.9 = f32[8788,128]{1,0:T(8,128)} fusion(...), kind=...``
    -> ``fusion.9 f32[8788,128] fusion``: name, result shape, opcode."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not rest:
        return name
    depth, shape, i = 0, "", 0
    for i, ch in enumerate(rest):  # the result shape ends at a top-level space
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == " " and depth == 0:
            break
        shape += ch
    opcode = rest[i:].strip().split("(", 1)[0]
    shape = re.sub(r"\{[^{}]*\}", "", shape)
    return f"{name} {shape} {opcode}".strip()


def self_time_by_name(events: Sequence[Event]) -> Dict[str, float]:
    """Summed self time (ns) of each op: its duration less that of the
    ops nested in it (a ``while`` op holds its body's ops)."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, child_ns]

    def close(item):
        out[item[0]] = out.get(item[0], 0.0) + item[3] - item[2]

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([op_name(name), e, 0.0, e - s])
    while stack:
        close(stack.pop())
    return out


def innermost(spans: Sequence[Event], t: float) -> str:
    """Name of the shortest span that holds time ``t`` ("none" if none)."""
    best: Optional[Event] = None
    for sp in spans:
        if sp[1] <= t <= sp[2] and (best is None
                                    or sp[2] - sp[1] < best[2] - best[1]):
            best = sp
    return best[0] if best is not None else "none"


@dataclasses.dataclass
class Reduction:
    """One traced window: device ops per chip and the host's spans."""

    device_ops: Dict[str, List[Event]]
    host_spans: List[Event]
    window_s: float

    @property
    def ops(self) -> List[Event]:
        return [e for evs in self.device_ops.values() for e in evs]

    @property
    def busy_s(self) -> float:
        """Seconds in which a leaf op ran, averaged over the chips
        traced."""
        if not self.device_ops:
            return 0.0
        return sum(busy_ns(evs) for evs in self.device_ops.values()) / (
            1e9 * len(self.device_ops))

    @property
    def idle_share(self) -> Optional[float]:
        """1 - busy / window, or None where no device op was traced."""
        if not self.ops or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def seconds_where(self, pred) -> float:
        """Device seconds of the ops whose text satisfies ``pred``,
        averaged over the chips traced."""
        if not self.device_ops:
            return 0.0
        return sum(e - s for n, s, e in self.ops if pred(n)) / (
            1e9 * len(self.device_ops))

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time, and the longest idle gaps
        named by the innermost host span that holds each gap's middle."""
        by_name = sorted(self_time_by_name(self.ops).items(),
                         key=lambda kv: -kv[1])
        idle = []
        for evs in self.device_ops.values():
            for s, e in gaps(evs):
                idle.append((innermost(self.host_spans, (s + e) / 2), e - s))
        idle.sort(key=lambda kv: -kv[1])
        return {"device_ops": [[n, t / 1e9] for n, t in by_name[:top]],
                "idle_gaps": [[n, t / 1e9] for n, t in idle[:top]]}


def load(trace_dir: str, window_s: float) -> Reduction:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {files}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    device_ops[plane.name] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns)
                                for ev in line.events)
    return Reduction(device_ops, host, window_s)
