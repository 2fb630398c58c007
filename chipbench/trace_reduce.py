"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read.

Read with `jax.profiler.ProfileData`, a trace has device planes
(`/device:TPU:<n>`) whose `XLA Ops` line holds one event per operation
the device ran and whose `XLA Modules` line holds one per program
execution, and host planes (`/host:...`) whose events are the host's
spans: the runtime's own and every `jax.profiler.TraceAnnotation`,
`repro.obs` spans among them. All share one clock.

The reduction, over the window that the host span `WINDOW` marks:

* busy: the union of the device's operation intervals; idle is the
  window less busy (averaged over devices);
* time per program: the module events' durations, summed by name;
* the operations that took most device time, by self time (an
  operation's time less that of the operations it contains, as a while
  loop contains its body), summed by a short label (the instruction
  name and its result shape, without layouts);
* the longest idle gaps of device 0, each named after what the host
  was doing: the shortest host span that covers the whole gap, else
  the shortest that covers its midpoint, else `no host span` (the
  host may still be running Python that opens no span).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

#: the host span a run opens around its measured window
WINDOW = "bench.window"

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Trace:
    """Intervals in ns: device ops and modules per device, host spans."""

    ops: dict = dataclasses.field(default_factory=dict)      # dev -> list
    modules: dict = dataclasses.field(default_factory=dict)  # dev -> list
    host: list = dataclasses.field(default_factory=list)     # (s, e, name)


def from_profile(pd) -> Trace:
    tr = Trace()
    for plane in pd.planes:
        dev = _DEVICE.match(plane.name)
        if dev:
            d = int(dev.group(1))
            for line in plane.lines:
                dest = {OPS_LINE: tr.ops, MODULES_LINE: tr.modules}.get(
                    line.name)
                if dest is None:
                    continue
                dest.setdefault(d, []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events)
    return tr


def read_file(path) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)))


def read_text_proto(text: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_text_proto(text))


def union(intervals, lo: float, hi: float) -> list:
    """Sorted disjoint (start, end) covering `intervals` within [lo, hi]."""
    out = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def gaps(busy: list, lo: float, hi: float) -> list:
    """The (start, end) intervals of [lo, hi] that `busy` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def base_name(module: str) -> str:
    """A module event's program name, without the `(id)` the profiler
    appends."""
    return module.split("(", 1)[0]


def short_op(name: str) -> str:
    """`%fusion.3 = u8[4,16]{1,0:T(8,128)} fusion(...)` -> `%fusion.3
    u8[4,16] fusion(...)`, at most 100 characters."""
    left, _, right = name.partition(" = ")
    return " ".join(filter(None, (left, re.sub(r"\{[^{}]*\}", "",
                                                right))))[:100]


def self_times(events) -> dict:
    """{name: duration less the durations of the events nested in it}."""
    out = defaultdict(float)
    stack = []  # [end, name, nested, duration]

    def close(top):
        out[top[1]] += top[3] - top[2]

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += min(e, stack[-1][0]) - s
        stack.append([e, name, 0.0, e - s])
    while stack:
        close(stack.pop())
    return out


def label_gap(host: list, s: float, e: float) -> str:
    whole = [h for h in host if h[0] <= s and h[1] >= e]
    if not whole:
        mid = (s + e) / 2
        whole = [h for h in host if h[0] <= mid <= h[1]]
    if not whole:
        return "no host span"
    return min(whole, key=lambda h: h[1] - h[0])[2]


def window_of(tr: Trace, name: str = WINDOW):
    spans = [h for h in tr.host if h[2] == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    s, e, _ = max(spans, key=lambda h: h[1] - h[0])
    return s, e


def reduce(tr: Trace, step_modules=(), top: int = 10) -> dict:
    """The window's device numbers (seconds): `window_s`, `busy_s`
    (mean over devices), `idle_share` (0..1), `module_s` {program:
    s}, `step_s` (the programs named in `step_modules`), `top_ops`
    and `idle_gaps` ([name, s] lists, longest first)."""
    lo, hi = window_of(tr)
    devices = sorted(tr.ops) or [0]
    busy_by_dev = {d: union(tr.ops.get(d, []), lo, hi) for d in devices}
    busy = sum(sum(e - s for s, e in b) for b in busy_by_dev.values())
    busy /= len(devices)
    module_s = defaultdict(float)
    for d in devices:
        for s, e, name in tr.modules.get(d, []):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                module_s[base_name(name)] += (e - s) / len(devices)
    op_s = defaultdict(float)
    for d in devices:
        clipped = [(max(s, lo), min(e, hi), name)
                   for s, e, name in tr.ops.get(d, []) if e > lo and s < hi]
        for name, t in self_times(clipped).items():
            op_s[short_op(name)] += t / len(devices)
    host = [h for h in tr.host if h[2] != WINDOW]
    idle = sorted(gaps(busy_by_dev[devices[0]], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    ns = 1e-9
    window_s = (hi - lo) * ns
    return {
        "window_s": window_s,
        "busy_s": busy * ns,
        "idle_share": 1.0 - busy * ns / window_s,
        "module_s": {k: v * ns for k, v in module_s.items()},
        "step_s": sum(v for k, v in module_s.items()
                      if k in set(step_modules)) * ns,
        "top_ops": [[k, v * ns] for k, v in sorted(
            op_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label_gap(host, s, e), (e - s) * ns]
                      for s, e in idle],
    }
