"""From a profiler trace to device busy time, op counts, kernel time and
named idle gaps.

The reduction works on plain interval lists (``Trace``), so it is tested on
hand-built traces; ``read_xplane`` fills one from the ``.xplane.pb`` file
that ``jax.profiler`` writes.  Times are nanoseconds on the trace's clock.
A chip is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per operation run (``XLA Modules``, one per program run).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_EVENT = "bench.window"


@dataclasses.dataclass
class Op:
    start: float
    end: float
    name: str
    module: str = ""    # device ops: the program that ran them
    thread: str = ""    # host events: the trace line (thread) they are on


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Op]]          # chip id -> ops
    programs: Dict[int, List[Op]]         # chip id -> program runs
    host: List[Op]                        # host events, any thread

    @property
    def device_events(self) -> int:
        """Op and program events kept on every chip's plane: what the
        profiler held of the device, which it caps."""
        return (sum(map(len, self.devices.values()))
                + sum(map(len, self.programs.values())))

    def window(self) -> Tuple[float, float]:
        """(start, end) of the benchmark's own window annotation."""
        spans = [(e.start, e.end) for e in self.host if e.name == WINDOW_EVENT]
        if not spans:
            raise ValueError(f"no {WINDOW_EVENT!r} event in the trace")
        return min(s for s, _ in spans), max(e for _, e in spans)


def _clip(ops: List[Op], lo: float, hi: float) -> np.ndarray:
    iv = np.array([(max(o.start, lo), min(o.end, hi)) for o in ops
                   if o.end > lo and o.start < hi], dtype=np.float64)
    return iv.reshape(-1, 2)


def merged(intervals: np.ndarray) -> np.ndarray:
    """Union of (start, end) intervals as disjoint sorted intervals."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, dtype=np.float64)


def busy_ns(ops: List[Op], lo: float, hi: float) -> float:
    """Time in [lo, hi] during which at least one op ran."""
    u = merged(_clip(ops, lo, hi))
    return float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0


def idle_gaps(ops: List[Op], lo: float, hi: float) -> np.ndarray:
    """(start, end) of every stretch of [lo, hi] in which no op ran."""
    u = merged(_clip(ops, lo, hi))
    edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def count_in(ops: List[Op], lo: float, hi: float) -> int:
    """Events that start inside [lo, hi)."""
    return sum(1 for o in ops if lo <= o.start < hi)


def time_matching(ops: List[Op], pattern: str, lo: float, hi: float) -> float:
    """Summed duration in [lo, hi] of ops whose name contains ``pattern``."""
    return float(sum(min(o.end, hi) - max(o.start, lo) for o in ops
                     if pattern in o.name and o.end > lo and o.start < hi))


def top_ops(trace: Trace, chips: List[int], lo: float, hi: float,
            n: int = 10) -> List[list]:
    """[[program:op, seconds per chip], ...], most time first."""
    tot: Dict[str, float] = defaultdict(float)
    for c in chips:
        for o in trace.devices.get(c, []):
            if o.end > lo and o.start < hi:
                key = f"{o.module}:{o.name}" if o.module else o.name
                tot[key] += (min(o.end, hi) - max(o.start, lo)) / 1e9
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(len(chips), 1)] for k, v in ranked]


def name_gaps(gaps: np.ndarray, host: List[Op], *, longest: int = 1000,
              n: int = 10) -> List[list]:
    """Idle seconds by what the host was doing: each of the ``longest``
    gaps is named by the shortest event of a Python thread (a JAX call, or
    the benchmark's own annotation around a request) that spans its
    midpoint, else by the shortest host event of any thread there, and the
    seconds are summed by name.  [[name, seconds], ...], most first."""
    if len(gaps) == 0:
        return []
    ev = [e for e in host if e.name != WINDOW_EVENT and e.end > e.start]
    starts = np.array([e.start for e in ev], np.float64)
    ends = np.array([e.end for e in ev], np.float64)
    lengths = ends - starts
    python = np.array([e.thread.startswith("python") for e in ev], bool)
    order = np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")[:longest]
    tot: Dict[str, float] = defaultdict(float)
    for s, e in gaps[order]:
        mid = 0.5 * (s + e)
        over = (starts <= mid) & (ends >= mid)
        hit = np.flatnonzero(over & python)
        if not len(hit):
            hit = np.flatnonzero(over)
        name = (ev[hit[np.argmin(lengths[hit])]].name if len(hit)
                else "no host event")
        tot[name] += (e - s) / 1e9
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


@dataclasses.dataclass
class Summary:
    """What the per-layer readers take from one traced window."""

    window_s: float
    chips: List[int]
    busy_s: Dict[int, float]
    ops: Dict[int, int]
    programs: Dict[int, int]
    kernel_s: Dict[str, float]              # pattern -> seconds, all chips
    device_ops: List[list]
    idle_gaps: List[list]
    bounds_ns: Tuple[float, float]
    events: int = 0                         # the trace's device events

    @property
    def mean_busy_s(self) -> float:
        return float(np.mean([self.busy_s[c] for c in self.chips]))


def summarize(trace: Trace, chips: List[int],
              kernels: Tuple[str, ...] = ("pairwise_lp",)) -> Summary:
    lo, hi = trace.window()
    busy = {c: busy_ns(trace.devices.get(c, []), lo, hi) / 1e9 for c in chips}
    gaps = idle_gaps(trace.devices.get(chips[0], []), lo, hi)
    return Summary(
        window_s=(hi - lo) / 1e9,
        chips=list(chips),
        busy_s=busy,
        ops={c: count_in(trace.devices.get(c, []), lo, hi) for c in chips},
        programs={c: count_in(trace.programs.get(c, []), lo, hi)
                  for c in chips},
        kernel_s={k: sum(time_matching(trace.devices.get(c, []), k, lo, hi)
                         for c in chips) / 1e9 for k in kernels},
        device_ops=top_ops(trace, chips, lo, hi),
        idle_gaps=name_gaps(gaps, trace.host),
        bounds_ns=(lo, hi),
        events=trace.device_events,
    )


def op_name(text: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def program_name(text: str) -> str:
    """``jit_pack_sketch(30898940)`` -> ``jit_pack_sketch``."""
    return text.split("(", 1)[0]


def attribute(ops: List[Op], programs: List[Op]) -> None:
    """Set each op's ``module`` to the program run that contains it."""
    if not programs or not ops:
        return
    progs = sorted(programs, key=lambda p: p.start)
    starts = np.array([p.start for p in progs], np.float64)
    where = np.searchsorted(starts, [o.start for o in ops], side="right") - 1
    for o, i in zip(ops, where):
        if i >= 0 and o.start < progs[i].end:
            o.module = progs[i].name


def read_xplane(path: Path) -> Trace:
    """The device and host events of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[int, List[Op]] = {}
    programs: Dict[int, List[Op]] = {}
    host: List[Op] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[chip] = [
                        Op(e.start_ns, e.start_ns + e.duration_ns,
                           op_name(e.name)) for e in line.events]
                elif line.name == MODULES_LINE:
                    programs[chip] = [
                        Op(e.start_ns, e.start_ns + e.duration_ns,
                           program_name(e.name)) for e in line.events]
            attribute(devices.get(chip, []), programs.get(chip, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Op(e.start_ns, e.start_ns + e.duration_ns,
                               e.name, thread=line.name)
                            for e in line.events if e.duration_ns > 0)
    return Trace(devices=devices, programs=programs, host=host)


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]
