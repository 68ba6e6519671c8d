"""Reduction of a profiler trace (``.xplane.pb``) to device time.

``load`` flattens the trace into :class:`Event` rows; ``reduce`` works
on rows only, so that it can be checked on a small trace written by
hand.  Within the slice the harness marks with a ``bench.slice`` host
span, per device plane (``/device:TPU:<n>``):

  busy       union of the intervals in which an op ran ("XLA Ops" line)
  ops        each op's own time: its interval less the ops nested in it
             (a while loop's events hold its body's ops)
  modules    own time of the ops inside each jitted program, by its
             name ("jit_prefill", "jit_decode", ...), and its executions
  collective device time of collective ops (all-to-all, collective-
             permute, all-gather, all-reduce, reduce-scatter) by module
  gaps       each stretch of 10 us or more with no op running, labelled
             with the innermost host span open at its middle (the
             harness's own annotations); shorter ones summed apart

Numbers of several devices are averaged over the devices.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

SLICE = "bench.slice"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SHORT_GAP_NS = 10_000        # shorter idle stretches are summed apart
SHORT_GAPS = "gaps_under_10us"
COLLECTIVE = re.compile(   # matched on the op's name, "_" read as "-"
    r"(all-to-all|collective-permute|all-gather|all-reduce|reduce-scatter)")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""          # the op's program, where the trace says it

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def latest(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_names: frozenset) -> list[Event]:
    """Device ops and modules of every device plane, and the host spans
    named in ``host_names``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and ev.name not in host_names:
                    continue
                module = ""
                if device:
                    stats = dict(ev.stats)
                    module = str(stats.get("hlo_module", ""))
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 module))
    return out


def module_kind(name: str) -> str:
    """'jit_prefill(42)' -> 'jit_prefill'."""
    return name.split("(")[0].strip()


def op_name(text: str) -> str:
    """'%fusion.12 = bf16[8]{0} fusion(...), kind=kLoop' -> 'fusion.12'
    (a TPU trace names an op by its whole HLO instruction)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def collective_kind(name: str) -> str | None:
    m = COLLECTIVE.match(op_name(name).replace("_", "-"))
    return m.group(1) if m else None


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduction:
    devices: int
    window_s: float
    busy_s: float                      # per device, averaged
    module_s: dict                     # module -> device seconds / device
    module_runs: dict                  # module -> executions / device
    collective_s: dict                 # module -> collective s / device
    op_s: dict                         # "module/op" -> seconds / device
    gap_s: dict                        # host span -> idle seconds, device 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(events: list[Event]) -> Reduction:
    slices = [e for e in events if e.name == SLICE
              and not DEVICE_PLANE.match(e.plane)]
    if not slices:
        raise ValueError(f"the trace holds no {SLICE!r} span")
    w0, w1 = slices[0].start_ns, slices[0].end_ns
    planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    if not planes:
        raise ValueError("the trace holds no device plane")
    host = [e for e in events if not DEVICE_PLANE.match(e.plane)
            and e.name != SLICE]
    n = len(planes)
    busy = 0.0
    module_s = collections.Counter()
    module_runs = collections.Counter()
    collective_s = collections.Counter()
    op_s = collections.Counter()
    gap_s = collections.Counter()
    for i, plane in enumerate(planes):
        mods = sorted((e for e in events if e.plane == plane
                       and e.line == MODULES_LINE
                       and w0 <= e.start_ns < w1), key=lambda e: e.start_ns)
        for m in mods:
            module_runs[module_kind(m.name)] += 1 / n
        starts = [m.start_ns for m in mods]
        ops = [e for e in events if e.plane == plane and e.line == OPS_LINE
               and e.end_ns > w0 and e.start_ns < w1]
        clipped = [(max(op.start_ns, w0), min(op.end_ns, w1), op)
                   for op in ops]
        for (s, t, op), own in zip(clipped, _self_times(clipped)):
            module = (module_kind(op.module) if op.module
                      else _enclosing(mods, starts, op.start_ns))
            secs = own * 1e-9 / n
            module_s[module] += secs
            op_s[f"{module}/{op_name(op.name)}"] += secs
            if collective_kind(op.name):
                collective_s[module] += secs
        merged = _union((s, t) for s, t, _ in clipped)
        busy += sum(t - s for s, t in merged) * 1e-9 / n
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for s, t in zip(edges[::2], edges[1::2]):
                if t - s >= SHORT_GAP_NS:
                    gap_s[_innermost(host, (s + t) / 2)] += (t - s) * 1e-9
                elif t > s:
                    gap_s[SHORT_GAPS] += (t - s) * 1e-9
    return Reduction(devices=n, window_s=(w1 - w0) * 1e-9, busy_s=busy,
                     module_s=dict(module_s), module_runs=dict(module_runs),
                     collective_s=dict(collective_s), op_s=dict(op_s),
                     gap_s=dict(gap_s))


def _self_times(clipped: list) -> list:
    """Each op's time less that of the ops nested in it (a while loop
    holds its body's ops), so that no device time counts twice.
    ``clipped``: (start, end, op) rows of one device line.  Ops of one
    line run one at a time unless nested, so an op that starts inside
    another is nested in it, though its end may pass the other's by the
    rounding of picoseconds to float nanoseconds: only the part inside
    is taken from the outer op."""
    order = sorted(range(len(clipped)),
                   key=lambda i: (clipped[i][0], -clipped[i][1]))
    own = [t - s for s, t, _ in clipped]
    stack = []
    for i in order:
        s, t, _ = clipped[i]
        while stack and clipped[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(t, clipped[stack[-1]][1]) - s
        stack.append(i)
    return own


def _enclosing(mods: list[Event], starts: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < mods[i].end_ns:
        return module_kind(mods[i].name)
    return "?"


def _innermost(spans: list[Event], t: float) -> str:
    best = None
    for s in spans:
        if s.start_ns <= t < s.end_ns and (best is None
                                           or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else "host.unmarked"
