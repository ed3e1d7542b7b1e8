"""Reduce a JAX profiler trace (`.xplane.pb`) to what the metrics read.

From the device planes: every program execution ("XLA Modules") and every
operation inside one ("XLA Ops"), with its program attributed by time.
From the host plane: the harness's own spans (`bench.*`), which bound
the measured window and label the device's idle gaps by what the host
was doing in them.

Busy time is the union of operation intervals inside the window,
averaged over the chips traced; idle is the rest of the window.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from . import names

NS = 1e-9
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Op:
    name: str                 # the operation's name in its program
    kernel: Optional[str]     # the Pallas kernel it runs, if any
    program: str              # the program it ran inside
    start: int                # ns
    end: int


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    programs: Dict[str, List[float]]          # name -> per-run seconds
    ops: List[Op]
    gaps: List[Tuple[str, float]]             # (host label, seconds)
    chips: int

    def program_seconds(self, pattern: str) -> List[float]:
        """Per-execution device seconds of the programs whose name matches
        `pattern` (a regular expression)."""
        out = []
        for name, runs in self.programs.items():
            if re.search(pattern, name):
                out.extend(runs)
        return out

    def kernel_seconds(self, kernel: str, program: str) -> Tuple[int, float]:
        """(calls, seconds) of Pallas kernel `kernel` (regular expression)
        inside programs matching `program`."""
        n, s = 0, 0.0
        for op in self.ops:
            if (op.kernel and re.search(kernel, op.kernel)
                    and re.search(program, op.program)):
                n += 1
                s += (op.end - op.start) * NS
        return n, s

    def breakdown(self) -> dict:
        by = defaultdict(float)
        for op in self.ops:
            if op.kernel is None and _CONTAINER.search(op.name):
                continue        # a loop or call whose body ops count
            by[op.kernel or _short(op.name)] += (op.end - op.start) * NS
        top = sorted(by.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


_CONTAINER = re.compile(r"^%[\w.-]+ = .*? (while|conditional|call)\(")
def _short(text: str) -> str:
    """An operation's HLO text cut to 'name shape opcode', its instance
    number dropped, so that one operation of every layer adds up."""
    head, _, rest = text.partition(" = ")
    name = re.sub(r"\.\d+$", "", head.lstrip("%"))
    shape = "(tuple)" if rest.startswith("(") else re.match(
        r"[^{ ]*", rest).group(0)
    op = re.search(r"[\]})] ([\w-]+)\(", rest)
    return f"{name} {shape} {op.group(1) if op else ''}".strip()


def find_trace(directory) -> Path:
    hits = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return Path(hits[-1])


def _union(intervals):
    total, cur_s, cur_e = 0, None, None
    merged = []
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                merged.append((cur_s, cur_e))
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        merged.append((cur_s, cur_e))
        total += cur_e - cur_s
    return total, merged


def reduce(path, chips: int = 1) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host_spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                # the thread that drives the engine: the one holding the
                # window span; Python function events ("$...") left out
                spans = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                         for ev in line.events
                         if not ev.name.startswith("$")]
                if any(sp[0] == WINDOW_SPAN for sp in spans):
                    host_spans = spans
        elif re.match(r"/device:TPU:\d+$", plane.name):
            devices.append(plane)
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    devices = sorted(devices, key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = devices[:chips]
    win = [s for s in host_spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = win[0][1], win[0][2]

    programs: Dict[str, List[float]] = defaultdict(list)
    ops: List[Op] = []
    busy_total = 0
    gaps: List[Tuple[str, float]] = []
    last_end = w1
    for plane in devices:
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                for ev in lines.get("XLA Modules", [])
                if int(ev.end_ns) > w0]
        for name, s, e in mods:
            programs[name].append((e - s) * NS)
            last_end = max(last_end, e)
        mods.sort(key=lambda m: m[1])
        starts = [m[1] for m in mods]
        ivals = []
        for ev in lines.get("XLA Ops", []):
            s, e = int(ev.start_ns), int(ev.end_ns)
            if e <= w0:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][0] if i >= 0 and mods[i][2] >= e else "?"
            ops.append(Op(ev.name, names.kernel_of(ev.name), prog, s, e))
            ivals.append((max(s, w0), e))
        busy, merged = _union(ivals)
        busy_total += busy
        if plane is devices[0]:
            prev = w0
            for s, e in merged + [(max(last_end, w1), None)]:
                if s > prev:
                    gaps.append((_label(host_spans, prev, s), (s - prev) * NS))
                prev = max(prev, e) if e is not None else prev
    window = (max(last_end, w1) - w0) * NS
    return Reduction(window, busy_total * NS / len(devices), dict(programs),
                     ops, gaps, len(devices))


def _label(spans, s, e) -> str:
    """The innermost span of the engine's host thread (the harness's and
    the runtime's own) covering the gap's midpoint."""
    mid = (s + e) // 2
    inner = None
    for name, a, b in spans:
        if a <= mid <= b and name != WINDOW_SPAN:
            if inner is None or (b - a) < (inner[2] - inner[1]):
                inner = (name, a, b)
    return inner[0] if inner else "host: outside any span"
