"""From a profiler trace of the window to device time, per op and per gap.

`Trace.load` reads the newest `.xplane.pb` the JAX profiler wrote under a
directory and keeps two lists, both on the trace's one clock (ns):

  ops    (name, start, duration, device, kernel, self) of every op the
         devices ran: the events of each TPU plane's "XLA Ops" line, named
         by their HLO instruction, result type and kind
         (`fusion.12 = s32[2,524288] fusion`). A loop's event
         spans the events of its body, so `self` is the op's duration less
         that of the ops nested in it, and self times add up without
         counting a loop body twice. `kernel` is the Pallas kernel an op
         runs, from the map `kernel_map` builds out of the compiled
         program's HLO, or "" for an XLA op;
  spans  (name, start, duration) of the benchmark's own host spans (names
         starting with "bench."), which `jax.profiler.TraceAnnotation`
         writes into the same trace.

The reductions: busy seconds (the union of op intervals, averaged over
devices), self time by kernel, the ops with most self time, and the
longest idle gaps, each named by the host span that overlaps it most.
`Trace.from_json` reads the same lists back from a small recorded file,
which is how the tests check the reduction.
"""
from __future__ import annotations

import base64
import dataclasses
import glob
import json
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")


def instruction(event_name: str) -> str:
    """The HLO instruction name an op event carries ("%fusion.3 = f32[..."
    -> "fusion.3")."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def describe(event_name: str, limit: int = 96) -> str:
    """A short label of an op event: its instruction, result type without
    layouts and op kind ("fusion.3 = s32[2,524288] fusion")."""
    text = re.sub(r"\{[^{}]*\}", "", event_name)
    m = _INSTR.match(text)
    if not m:
        return text[:limit]
    rest, depth = text[m.end():].lstrip(), 0
    for i, ch in enumerate(rest):             # the type ends at depth 0
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0 and (ch == ")" or rest[i + 1:i + 2] == " "):
            break
    kind = re.match(r"\s*([\w\-]+)", rest[i + 1:])
    label = f"{m.group(1)} = {rest[:i + 1]}" + (f" {kind.group(1)}"
                                               if kind else "")
    return label[:limit]


def kernel_map(hlo_text: str) -> dict:
    """{instruction: kernel} of the Mosaic custom calls in a compiled
    program's HLO text. A Mosaic call carries its kernel as serialized MLIR
    (base64 in the backend config); the kernel is named by the Python
    function the module was traced from, the one string in it that is an
    identifier ending in `_kernel`."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        names = []
        if body:
            raw = base64.b64decode(body.group(1))
            names = [s.decode() for s in raw.split(b"\x00")
                     if re.fullmatch(rb"[A-Za-z_][A-Za-z0-9_]*_kernel", s)]
        out[instruction(line)] = names[0] if names else "tpu_custom_call"
    return out


def _self_times(events: list) -> list:
    """Self time of each (start, dur) event of one line: its duration less
    that of the events directly nested in it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [float(d) for _, d in events]
    stack: list = []
    for i in order:
        s, d = events[i]
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return own


@dataclasses.dataclass
class Trace:
    ops: list          # [(name, start_ns, dur_ns, device, kernel, self_ns)]
    spans: list        # [(name, start_ns, dur_ns)]
    window: tuple      # (start_ns, end_ns)

    @classmethod
    def load(cls, directory: str, kernels: dict):
        """Read the newest xplane file under `directory`; `kernels` maps
        instruction names to Pallas kernels (`kernel_map`)."""
        from jax.profiler import ProfileData
        files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        data = ProfileData.from_file(max(files, key=os.path.getmtime))
        ops, spans = [], []
        devices = sorted(p.name for p in data.planes
                         if DEVICE_PLANE.match(p.name))
        for plane in data.planes:
            if plane.name in devices:
                dev = devices.index(plane.name)
                for line in plane.lines:
                    if line.name != OP_LINE:
                        continue
                    evs = [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
                    own = _self_times([(s, d) for _, s, d in evs])
                    ops += [(describe(n), s, d, dev,
                             kernels.get(instruction(n), ""), o)
                            for (n, s, d), o in zip(evs, own)]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(e.name, e.start_ns, e.duration_ns)
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
        return cls.build(ops, spans)

    @classmethod
    def build(cls, ops, spans):
        """The window is the `bench.window` span, else the ops' extent."""
        win = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN]
        if win:
            window = win[0]
        elif ops:
            window = (min(o[1] for o in ops), max(o[1] + o[2] for o in ops))
        else:
            window = (0.0, 0.0)
        return cls(ops=[tuple(o) for o in ops],
                   spans=[tuple(s) for s in spans], window=tuple(window))

    @classmethod
    def from_json(cls, path):
        with open(path) as f:
            d = json.load(f)
        return cls(ops=[tuple(o) for o in d["ops"]],
                   spans=[tuple(s) for s in d["spans"]],
                   window=tuple(d["window"]))

    def to_json(self, path):
        with open(path, "w") as f:
            json.dump(dict(ops=self.ops, spans=self.spans,
                           window=self.window), f)

    # -- reductions ---------------------------------------------------------

    @property
    def devices(self) -> int:
        return len({o[3] for o in self.ops}) or 1

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _in_window(self):
        """(op, share of it inside the window) of the ops that overlap it."""
        lo, hi = self.window
        for o in self.ops:
            s, e = max(o[1], lo), min(o[1] + o[2], hi)
            if e > s:
                yield o, (e - s) / o[2]

    def busy_intervals(self, device: int) -> list:
        """Merged [start, end) intervals in which an op ran on `device`,
        clipped to the window."""
        lo, hi = self.window
        iv = sorted((max(o[1], lo), min(o[1] + o[2], hi))
                    for o, _ in self._in_window() if o[3] == device)
        merged: list = []
        for s, e in iv:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    @property
    def busy_s(self) -> float:
        """Seconds of the window in which some op ran, averaged over the
        devices."""
        devs = sorted({o[3] for o in self.ops})
        total = sum(e - s for d in devs for s, e in self.busy_intervals(d))
        return total * 1e-9 / max(len(devs), 1)

    def op_seconds(self, kernel=None) -> float:
        """Self seconds in the window of one Pallas kernel's ops (by kernel
        name), of every XLA op (kernel=""), or of all ops (None), averaged
        over the devices."""
        total = sum(o[5] * share for o, share in self._in_window()
                    if kernel is None or o[4] == kernel)
        return total * 1e-9 / self.devices

    def top_ops(self, n: int = 10) -> list:
        """[[name, seconds], ...] of the ops with most self time in the
        window (a Pallas op under its kernel's name), largest first."""
        acc: dict = {}
        for o, share in self._in_window():
            key = o[4] or o[0]
            acc[key] = acc.get(key, 0.0) + o[5] * share * 1e-9 / self.devices
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """[[host span, seconds], ...] of the n longest stretches of the
        window with no op on device 0, each named by the benchmark span
        that overlaps it most ("none" where no span does)."""
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy_intervals(0):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            best, name = 0.0, "none"
            for sn, ss, sd in self.spans:
                ov = min(e, ss + sd) - max(s, ss)
                if sn != WINDOW_SPAN and ov > best:
                    best, name = ov, sn
            out.append([name, (e - s) * 1e-9])
        return out
