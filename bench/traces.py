"""Reading a profiler trace of the measured window.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  A TPU chip is a plane named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed HLO
operation and its line ``XLA Modules`` one event per executed program.  The
host's planes (``/host:...``) hold the benchmark's own ``bench_window``
annotation, which bounds the window every reduction is clipped to.

``Trace`` keeps only what the reductions read, as plain tuples, so that it
can be saved to and rebuilt from JSON (the recorded trace of the tests).
Every time is in seconds on the trace's clock.
"""
from __future__ import annotations

import glob
import json
import os
import re
import shutil
from typing import Callable, Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
Span = Tuple[float, float, str]           # start, end, name


class MissingOp(RuntimeError):
    """A reduction found no operation of the name it needs."""


_HLO_HEAD = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
_ATTRS = re.compile(r"\b(kind|calls|custom_call_target)=\"?(%?[\w.\-]+)")
CONTAINERS = ("while", "conditional", "call")


def compact_name(text: str) -> str:
    """An ``XLA Ops`` event's name is the HLO instruction's whole text;
    keep ``<instruction> = <opcode>`` and the attributes that say what it
    runs (``kind``, ``calls``, ``custom_call_target``)."""
    head = _HLO_HEAD.match(text)
    op = _OPCODE.search(text, head.end()) if head else None
    if not op:
        return text[:200]
    attrs = " ".join(f"{k}={v}" for k, v in _ATTRS.findall(text))
    return f"{head.group(1)} = {op.group(1)}" + (f" {attrs}" if attrs else "")


def instruction(name: str) -> str:
    """``fusion.241`` of ``fusion.241 = fusion kind=kOutput ...``."""
    return name.split(" = ", 1)[0]


def opcode(name: str) -> str:
    parts = name.split(" = ", 1)
    return parts[1].split(" ", 1)[0] if len(parts) == 2 else ""


def _union(intervals, lo, hi) -> List[Tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


class Trace:
    """Device operations and programs per chip, host spans, and the
    window."""

    def __init__(self, window: Tuple[float, float], ops: Dict[int, List[Span]],
                 modules: Dict[int, List[Span]], host: List[Span]):
        self.window = (float(window[0]), float(window[1]))
        self.ops = {int(d): sorted(map(tuple, v)) for d, v in ops.items()}
        self.modules = {int(d): sorted(map(tuple, v))
                        for d, v in modules.items()}
        self.host = sorted(map(tuple, host))
        if not any(self.ops.values()):
            raise MissingOp("the trace holds no device operation")

    # -- persistence ---------------------------------------------------------

    def to_json(self) -> dict:
        return dict(window=self.window, ops=self.ops, modules=self.modules,
                    host=self.host)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(d["window"], d["ops"], d["modules"], d["host"])

    # -- reductions ----------------------------------------------------------

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _inside(self, s: float, e: float) -> bool:
        """Whether an event belongs to the window: its middle lies in it.
        The host's and the chips' clocks agree to well under a millisecond,
        so the window's first program can start a little before the host
        span that dispatched it."""
        return self.window[0] <= 0.5 * (s + e) <= self.window[1]

    def busy_intervals(self, device: int):
        return _union([(s, e) for s, e, _ in self.ops[device]],
                      *self.window)

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        return sum(sum(e - s for s, e in self.busy_intervals(d))
                   for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def gaps(self, device: int) -> List[Tuple[float, float]]:
        """Idle intervals of one chip inside the window."""
        lo, hi = self.window
        out, t = [], lo
        for s, e in self.busy_intervals(device):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def op_seconds(self, match: Callable[[str], bool], *,
                   required: str = "") -> Tuple[float, int]:
        """Total device seconds and count of the operations inside the
        window whose name ``match`` accepts, over all chips.
        With ``required``, finding none raises ``MissingOp``."""
        total, n = 0.0, 0
        for d in self.devices:
            for s, e, name in self.ops[d]:
                if self._inside(s, e) and match(name):
                    total += e - s
                    n += 1
        if required and n == 0:
            raise MissingOp(f"no device operation is {required}")
        return total, n

    def program_gaps(self, match: Callable[[str], bool]) -> List[float]:
        """Per chip, the idle time between one execution of a matching
        program and the next, inside the window."""
        out = []
        for d in self.devices:
            runs = [(s, e) for s, e, name in self.modules.get(d, [])
                    if self._inside(s, e) and match(name)]
            out.extend(b[0] - a[1] for a, b in zip(runs, runs[1:]))
        return out

    def host_activity(self, t0: float, t1: float) -> str:
        """The innermost host span that covers [t0, t1]'s midpoint."""
        mid = 0.5 * (t0 + t1)
        best = None
        for s, e, name in self.host:
            if s <= mid <= e and name != "bench_window" and \
                    (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "no host span"

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time (per chip on average)
        and the longest idle gaps with what the host was doing."""
        per_op: Dict[str, float] = {}
        for d in self.devices:
            for s, e, name in self.ops[d]:
                # a loop's or call's time is its body's: count the body
                if self._inside(s, e) and opcode(name) not in CONTAINERS:
                    per_op[name] = per_op.get(name, 0.0) + e - s
        n = len(self.devices)
        ops = sorted(((k, v / n) for k, v in per_op.items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = sorted(((self.host_activity(a, b), b - a)
                       for d in self.devices for a, b in self.gaps(d)),
                      key=lambda kv: -kv[1])[:top]
        return dict(device_ops=[list(x) for x in ops],
                    idle_gaps=[list(x) for x in gaps])


# ---------------------------------------------------------------------------
# reading an .xplane.pb
# ---------------------------------------------------------------------------

def load(path: str, window_name: str = "bench_window") -> Trace:
    """Parse one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Span]] = {}
    modules: Dict[int, List[Span]] = {}
    host: List[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            d = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[d] = [(e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9,
                               compact_name(e.name)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[d] = [(e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9,
                                   e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9, e.name)
                            for e in line.events)
    spans = [h for h in host if h[2] == window_name]
    if len(spans) != 1:
        raise MissingOp(f"the trace holds {len(spans)} {window_name!r} "
                        "host spans, expected 1")
    return Trace(spans[0][:2], ops, modules, host)


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise MissingOp(f"{len(paths)} .xplane.pb files under {directory}")
    return paths[0]


def load_dir(directory: str, window_name: str = "bench_window") -> Trace:
    return load(find_xplane(directory), window_name)


def remove_dir(directory: str):
    shutil.rmtree(directory, ignore_errors=True)


def save_json(trace: Trace, path: str):
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
