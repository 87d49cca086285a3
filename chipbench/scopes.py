"""From a traced window to the device time of each BSO-SL layer.

The program names each layer's work with a ``jax.named_scope``
(``SCOPES``) and each round on the host with a span ``bso.round``
holding ``bso.dispatch`` (the call of the round program) and
``bso.round_log`` (where the host blocks and reads the round back).

A TPU's op events carry only the text of the optimized HLO instruction
that ran, not its scope. The scope is in the instruction's metadata,
``metadata={op_name="jit(swarm_round)/bso.eval/..."}``, so an op's scope
is read by its name through the program's HLO text. The profiler keeps
each program it saw in the trace itself (an ``Hlo Proto`` per module on
the plane ``/host:metadata``), so the text read is that of the program
that ran, and a module's ops are found by time: they run inside the
module's event on the device's ``XLA Modules`` line.

Everything but ``load_programs`` and ``split`` works on plain text and
``Event`` tuples, so the reduction is tested on small synthetic traces.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from collections import defaultdict

from chipbench import trace

SCOPES = ("bso.local_phase", "bso.eval", "bso.stat_upload", "bso.kmeans",
          "bso.brain_storm", "bso.eq2")
COORDINATOR = ("bso.stat_upload", "bso.kmeans", "bso.brain_storm", "bso.eq2")
OTHER = "other"
ROUND_SPAN, DISPATCH_SPAN, LOG_SPAN = "bso.round", "bso.dispatch", "bso.round_log"

METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
HLO_PROTO_STAT = "Hlo Proto"

_SCOPE = re.compile(r"(?<![\w.])(" + "|".join(re.escape(s) for s in SCOPES)
                    + r")(?![\w.])")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s")
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation"
                    r"|false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)"
                         r"=\{([^}]*)\}")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def scope_of(op_name: str):
    """The innermost of ``SCOPES`` in an op_name path, or None."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: scope or None} of an HLO module's text. An
    instruction whose own op_name holds no scope takes the scope of the
    instruction that calls its computation (a while body's ``copy``
    takes the ``while``'s; a fused computation's ops, their fusion's);
    a computation called from several places takes its first caller's."""
    comps, entry, comp = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = comps.setdefault(m.group(1), [])
            if line.startswith("ENTRY"):
                entry = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        calls = _CALLS.findall(line)
        for group in _CALL_LISTS.findall(line):
            calls += [c.strip().lstrip("%") for c in group.split(",")
                      if c.strip()]
        op_name = _OP_NAME.search(line)
        comp.append((m.group(1), scope_of(op_name.group(1)) if op_name
                     else None, calls))
    out, seen, todo = {}, {entry}, [(entry, None)]
    while todo:
        name, inherited = todo.pop(0)
        for inst, own, calls in comps.get(name, ()):
            out[inst] = own or inherited
            for c in calls:
                if c not in seen:
                    seen.add(c)
                    todo.append((c, out[inst]))
    return out


def _module_at(modules, starts, t):
    """The module whose event covers time ``t``, from one device's
    ``XLA Modules`` events sorted by start (``starts``), or None."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i].end_ns > t:
        return modules[i].name
    return None


def scope_seconds(tr, maps: dict, modules: dict) -> dict:
    """{scope or ``OTHER``: device self time in seconds inside the
    window, averaged over devices}. ``maps``: module name -> its
    ``op_scopes``; ``modules``: device -> [Event] of its ``XLA Modules``
    line. An op inside no module of ``maps``, or with no scope, is
    ``OTHER``."""
    out = defaultdict(float)
    for dev, evs in tr.devices.items():
        mods = sorted(modules.get(dev, ()), key=lambda m: m.start_ns)
        starts = [m.start_ns for m in mods]
        labelled = []
        for e in evs:
            mid = e.start_ns + e.dur_ns / 2
            scopes = maps.get(_module_at(mods, starts, mid), {})
            name = trace.short_name(e.name).lstrip("%")
            labelled.append(e._replace(name=scopes.get(name) or OTHER))
        for label, sec in trace.self_times(labelled, tr.start_ns, tr.end_ns):
            out[label] += sec / len(tr.devices)
    return dict(out)


def idle_in_spans(tr, name: str) -> float:
    """Seconds in which the first device ran no op, inside both the
    window and the union of the host spans called ``name``."""
    dev = sorted(tr.devices)[0]
    busy = trace.busy_intervals(tr.devices[dev], tr.start_ns, tr.end_ns)
    spans = trace.busy_intervals([e for e in tr.host if e.name == name],
                                 tr.start_ns, tr.end_ns)
    busy_in_spans = sum(max(0.0, min(t, b) - max(s, a))
                        for s, t in spans for a, b in busy)
    return (sum(t - s for s, t in spans) - busy_in_spans) * 1e-9


def clock_offsets(host, modules) -> list:
    """[(module start - dispatch start, round-log end - module end)] in
    ms, for each ``bso.round`` span on the host holding one
    ``bso.dispatch`` and one ``bso.round_log``, with the event of
    ``modules`` (one device's) that overlaps the span most. Both are
    >= 0 where the host spans and the device share a clock."""
    out = []
    for r in sorted((e for e in host if e.name == ROUND_SPAN),
                    key=lambda e: e.start_ns):
        inside = [e for e in host if r.start_ns <= e.start_ns
                  and e.end_ns <= r.end_ns]
        disp = [e for e in inside if e.name == DISPATCH_SPAN]
        log = [e for e in inside if e.name == LOG_SPAN]
        run = max(modules, default=None, key=lambda m: min(m.end_ns, r.end_ns)
                  - max(m.start_ns, r.start_ns))
        if len(disp) == 1 and len(log) == 1 and run is not None:
            out.append(((run.start_ns - disp[0].start_ns) * 1e-6,
                        (log[0].end_ns - run.end_ns) * 1e-6))
    return out


def _fields(buf: bytes):
    """(field number, value) of a serialized protocol buffer message;
    a length-delimited value is its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def hlo_modules(xspace: bytes) -> dict:
    """{module name: serialized HloModuleProto} of the programs the
    profiler recorded on the plane ``/host:metadata`` of a serialized
    ``XSpace``: per module an event metadata (``XEventMetadata``, field
    4 of ``XPlane``) whose stat ``Hlo Proto`` holds an ``HloProto``,
    whose field 1 is the module."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1:                                  # XSpace.planes
            continue
        name, stat_names, events = None, {}, []
        for g, v in _fields(plane):
            if g == 2:                              # XPlane.name
                name = v.decode()
                if name != METADATA_PLANE:
                    break
            elif g == 4:                            # event_metadata map
                events += [x for k, x in _fields(v) if k == 2]
            elif g == 5:                            # stat_metadata map
                for k, x in _fields(v):
                    if k == 2:
                        meta = dict(_fields(x))
                        stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
        if name != METADATA_PLANE:
            continue
        for ev in events:
            meta = {}
            for k, x in _fields(ev):
                if k == 2:                          # XEventMetadata.name
                    meta["name"] = x.decode()
                elif k == 5:                        # XEventMetadata.stats
                    stat = dict(_fields(x))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT:
                        meta["proto"] = dict(_fields(stat[6])).get(1)
            if meta.get("proto"):
                out[meta["name"]] = meta["proto"]
    return out


def hlo_text(module_proto: bytes) -> str:
    from jax._src.lib import xla_client
    return xla_client._xla.HloModule.from_serialized_hlo_module_proto(
        module_proto).to_string()


def newest_xplane(root) -> str | None:
    paths = glob.glob(os.path.join(str(root), "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load_programs(path: str, tr):
    """(maps, modules) for ``scope_seconds`` from the trace file at
    ``path``: every recorded module's ``op_scopes`` and each device's
    ``XLA Modules`` events. None where the file is not the trace of
    ``tr`` (its ``chipbench.window`` span starts elsewhere)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    profile = ProfileData.from_serialized_xspace(data)
    modules, window = {}, None
    for plane in profile.planes:
        if plane.name in tr.devices:
            modules[plane.name] = [
                trace.Event(e.name, e.start_ns, e.duration_ns)
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events]
        elif plane.name == trace.HOST_PLANE:
            window = next((e.start_ns for line in plane.lines
                           for e in line.events
                           if e.name == trace.WINDOW_SPAN), window)
    if window != tr.start_ns:
        return None
    maps = {name: op_scopes(hlo_text(proto))
            for name, proto in hlo_modules(data).items()}
    return maps, modules


_SPLITS: dict = {}


def split(ctx):
    """The traced window split by layer, per round: {scope or ``OTHER``:
    device ms}, ``idle_ms`` (all device idle), ``trainer_idle_ms``
    (device idle inside ``bso.round`` spans; None without them) and
    ``clock_offsets``. None where the trace has no device plane or the
    programs that ran in the window carry none of ``SCOPES`` (a
    program from before they were added)."""
    tr = ctx.trace
    if not tr.devices or ctx.rounds == 0:
        return None
    from chipbench.run import OUT_DIR
    path = newest_xplane(OUT_DIR / "trace")
    key = (path, tr.start_ns)
    if key not in _SPLITS:
        _SPLITS[key] = _split(tr, load_programs(path, tr) if path else None,
                              ctx.rounds)
    return _SPLITS[key]


def _split(tr, programs, rounds):
    if programs is None:
        return None
    maps, modules = programs
    ran = {m.name for evs in modules.values() for m in evs
           if m.end_ns > tr.start_ns and m.start_ns < tr.end_ns}
    scoped = {n for n in ran if any(maps.get(n, {}).values())}
    if not scoped:
        return None
    per_round = 1e3 / rounds
    out = {k: v * per_round for k, v in scope_seconds(tr, maps,
                                                      modules).items()}
    out["idle_ms"] = (tr.window_s - trace.mean_busy_s(tr)) * per_round
    has_rounds = any(e.name == ROUND_SPAN for e in tr.host)
    out["trainer_idle_ms"] = (idle_in_spans(tr, ROUND_SPAN) * per_round
                              if has_rounds else None)
    first = sorted(tr.devices)[0]
    out["clock_offsets"] = clock_offsets(
        tr.host, [m for m in modules.get(first, ()) if m.name in scoped])
    out["window_ms"] = tr.window_s * per_round
    print(f"[chipbench] scopes per round: {out}", file=sys.stderr, flush=True)
    return out


def scope_ms(ctx, *names):
    """Device ms a round in the scopes ``names``, or None."""
    s = split(ctx)
    if s is None:
        return None
    return sum(s.get(n, 0.0) for n in names)
