"""Profiler trace -> device busy time, time by program and op, idle gaps.

A trace is read into plain data, `[{"name": plane, "lines": [{"name":
line, "events": [[name, start_ns, duration_ns], ...]}]}]`, so that a
small recorded trace can be kept as a test fixture in the same form.

- Device planes are the `/device:TPU:<i>` planes. Their `XLA Ops` line
  gives the busy intervals (their union) and the time by op; their `XLA
  Modules` line gives the time by program (jitted function).
- The traced window is the host span `bench.window`, which the harness
  opens around the measured window.
- Each idle gap of a device inside the window is attributed to the
  harness host span (`bench.*`) that overlaps it most, or to `host.other`.
"""

from __future__ import annotations

import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def find_xplane(directory: str | Path) -> Path:
    paths = sorted(Path(directory).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load_xplane(path: str | Path) -> list[dict]:
    """Read a profiler .xplane.pb into plain planes/lines/events data."""
    from jax.profiler import ProfileData

    planes = []
    for pl in ProfileData.from_file(str(path)).planes:
        lines = []
        for ln in pl.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in ln.events]
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: int, e: int, lo: int, hi: int) -> int:
    return max(0, min(e, hi) - max(s, lo))


class Reduction:
    """What one trace says about the window it covers."""

    def __init__(self, planes: list[dict], window_span: str = WINDOW_SPAN,
                 host_prefix: str = "bench."):
        host = [(n, s, s + d) for pl in planes
                if not DEVICE_PLANE.match(pl["name"])
                for ln in pl["lines"] for n, s, d in ln["events"]
                if n.startswith(host_prefix)]
        windows = [(s, e) for n, s, e in host if n == window_span]
        if not windows:
            raise ValueError(f"trace holds no {window_span!r} span")
        self.lo, self.hi = windows[0][0], windows[-1][1]
        self.host_spans = [(n, s, e) for n, s, e in host
                           if n != window_span]
        self.devices = []
        for pl in planes:
            if not DEVICE_PLANE.match(pl["name"]):
                continue
            lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
            ops = [(n, s, s + d) for n, s, d in lines.get(OPS_LINE, [])]
            mods = [(n, s, s + d) for n, s, d in lines.get(MODULES_LINE, [])]
            if ops or mods:
                self.devices.append({"name": pl["name"], "ops": ops,
                                     "modules": mods})

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _busy(self, dev: dict) -> list[tuple[int, int]]:
        ev = dev["ops"] or dev["modules"]
        return [(max(s, self.lo), min(e, self.hi))
                for s, e in _union([(s, e) for _, s, e in ev])
                if e > self.lo and s < self.hi]

    @property
    def busy_s(self) -> float:
        """Seconds some op ran, averaged over the devices that ran any."""
        if not self.devices:
            return 0.0
        tot = sum(e - s for d in self.devices for s, e in self._busy(d))
        return tot / len(self.devices) / 1e9

    def _sum(self, kind: str, pattern: str) -> float:
        rx = re.compile(pattern)
        tot = sum(_clip(s, e, self.lo, self.hi)
                  for d in self.devices for n, s, e in d[kind]
                  if rx.search(n))
        return tot / max(len(self.devices), 1) / 1e9

    def program_s(self, pattern: str) -> float:
        """Device seconds of programs whose name matches `pattern`."""
        return self._sum("modules", pattern)

    def op_s(self, pattern: str) -> float:
        """Device seconds of ops whose name matches `pattern`."""
        return self._sum("ops", pattern)

    def top_ops(self, n: int = 10) -> list[list]:
        """[program/op, seconds] of the ops that took most device time;
        an op is named by its program (hash cut off) and its HLO name."""
        by: dict[str, int] = {}
        for d in self.devices:
            mods = sorted(d["modules"], key=lambda m: m[1])
            j = 0
            for name, s, e in sorted(d["ops"], key=lambda o: o[1]):
                while j < len(mods) and mods[j][2] <= s:
                    j += 1
                prog = mods[j][0].split("(")[0] \
                    if j < len(mods) and mods[j][1] <= s else "?"
                key = f"{prog}/{name.split(' = ')[0]}"
                by[key] = by.get(key, 0) + _clip(s, e, self.lo, self.hi)
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / max(len(self.devices), 1) / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """[host span, seconds] of the longest idle gaps in the window."""
        gaps = []
        for d in self.devices:
            t = self.lo
            for s, e in self._busy(d) + [(self.hi, self.hi)]:
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            best, name = 0, "host.other"
            for hn, hs, he in self.host_spans:
                ov = _clip(hs, he, s, e)
                if ov > best:
                    best, name = ov, hn
            out.append([name, (e - s) / 1e9])
        return out
