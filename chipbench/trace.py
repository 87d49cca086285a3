"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. On a TPU, each chip is a plane
named ``/device:TPU:<i>``, whose ``XLA Ops`` line holds one event per
operation the chip ran. The host is the plane ``/host:CPU``: one line
per thread, holding the benchmark's own ``TraceAnnotation`` spans and,
from the Python tracer, the functions the host was in.

Everything below works on plain ``Event`` tuples, so the reduction is
tested on small synthetic traces without a chip.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import NamedTuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "chipbench.window"


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Trace(NamedTuple):
    devices: dict             # device plane name -> [Event] of its ops
    host: list                # [Event] of every host thread
    start_ns: float           # the traced window, on the trace's clock
    end_ns: float

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def load(trace_dir: str) -> Trace:
    """The newest trace under ``trace_dir``, cut to the host span
    ``chipbench.window``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = [
                Event(e.name, e.start_ns, e.duration_ns)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name == HOST_PLANE:
            host += [Event(e.name, e.start_ns, e.duration_ns)
                     for line in plane.lines for e in line.events]
    return window(devices, host)


def window(devices: dict, host: list) -> Trace:
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, "
                         f"found {len(spans)}")
    w = spans[0]
    return Trace(devices, host, w.start_ns, w.end_ns)


def _clip(events, lo, hi) -> list:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((s, t))
    return out


def busy_intervals(events, lo, hi) -> list:
    """The union of the events' intervals inside [lo, hi], merged."""
    merged = []
    for s, t in sorted(_clip(events, lo, hi)):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return merged


def busy_s(tr: Trace, device: str) -> float:
    return sum(t - s for s, t in busy_intervals(
        tr.devices[device], tr.start_ns, tr.end_ns)) * 1e-9


def mean_busy_s(tr: Trace):
    """Busy seconds averaged over the traced devices (None: no device
    plane, as in a trace of the CPU)."""
    if not tr.devices:
        return None
    return sum(busy_s(tr, d) for d in tr.devices) / len(tr.devices)


def op_seconds(tr: Trace, match) -> float:
    """Summed device time, averaged over devices, of the ops whose name
    satisfies ``match`` (a callable on the name), inside the window."""
    if not tr.devices:
        return 0.0
    total = 0.0
    for evs in tr.devices.values():
        total += sum(t - s for s, t in _clip(
            [e for e in evs if match(e.name)], tr.start_ns, tr.end_ns))
    return total * 1e-9 / len(tr.devices)


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def self_times(events, lo, hi) -> list:
    """[(name, seconds)] of each event inside [lo, hi], less the time of
    the events nested in it (a loop's body ops, say)."""
    out, stack = [], []        # stack: [name, end, self_ns]
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        while stack and stack[-1][1] <= s:
            name, _, own = stack.pop()
            out.append((name, own * 1e-9))
        if t <= s:
            continue
        if stack:
            stack[-1][2] -= t - s
        stack.append([e.name, t, t - s])
    out += [(name, own * 1e-9) for name, _, own in stack]
    return out


def top_ops(tr: Trace, n: int = 10) -> list:
    """[[op name, seconds]]: the ops with most self time on the device,
    averaged over devices."""
    per = defaultdict(float)
    for evs in tr.devices.values():
        for name, sec in self_times(evs, tr.start_ns, tr.end_ns):
            per[short_name(name)] += sec / len(tr.devices)
    return [[k, v] for k, v in sorted(per.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """[[host activity, seconds]]: the device's idle time inside the
    window (first device), summed by what the host was doing in the
    middle of each gap: the shortest host event that covers it."""
    if not tr.devices:
        return []
    dev = sorted(tr.devices)[0]
    busy = busy_intervals(tr.devices[dev], tr.start_ns, tr.end_ns)
    edges = [tr.start_ns] + [x for iv in busy for x in iv] + [tr.end_ns]
    per = defaultdict(float)
    for s, t in zip(edges[::2], edges[1::2]):
        if t <= s:
            continue
        mid = (s + t) / 2
        covering = [e for e in tr.host if e.start_ns <= mid <= e.end_ns
                    and e.name != WINDOW_SPAN]
        name = min(covering, key=lambda e: e.dur_ns).name if covering \
            else "(no host span)"
        per[name] += (t - s) * 1e-9
    return [[k, v] for k, v in sorted(per.items(), key=lambda kv: -kv[1])[:n]]
