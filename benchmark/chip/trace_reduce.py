"""From a profiler trace (``.xplane.pb``) to numbers.

``load`` reads the file with ``jax.profiler.ProfileData`` and returns plain
data::

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [(name, start_ns, duration_ns), ...]}]}],
     "detail": {device event name: full text},
     "scope": {device event name: the path of scopes it was traced under}}

The TPU profiler names a device operation by its whole HLO instruction
(``%fusion.12 = (f32[4,512]...) fusion(...), kind=kLoop, ...``).  ``load``
shortens that to ``fusion.12 f32[4,512]`` and keeps the text under
``detail``, where a reader looks for what the short name does not say
(``custom_call_target="tpu_custom_call"`` marks a Pallas kernel).

The path of ``jit`` and ``jax.named_scope`` names an operation was traced
under (``jit(step)/transpose(jvp(jit(FullyConnected)))/dot_general:``: the
HLO ``op_name``) is not in that text.  The profiler keeps it as the
statistic ``tf_op`` of the event's metadata, which ``ProfileData`` does not
hand out (its ``stats`` are the event's own: offsets and durations; TPU v5e,
jax 0.9.0, PR 29), so ``load`` takes it from the file's wire format itself
(``_metadata_stat``) and keeps it under ``scope``.  A fusion carries one
path: a weight-gradient product that the compiler fused with its AdamW
update carries the product's.  Some of what the compiler adds itself
(copies, slices) carries none.

Every reduction below works on that, so a test can hand them a trace it
wrote itself (``write_xspace`` writes the same wire format, and is how the
fixture under ``fixtures/`` was cut from a chip run).

A device is a plane named ``/device:TPU:<n>``; its operations are the line
``XLA Ops`` and its programs the line ``XLA Modules``.  Host threads are
the lines of ``/host:CPU``; the benchmark's own ``TraceAnnotation``s are
the events there whose names start with ``bench:``.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MARK = "bench:"
SCOPE_STAT = "tf_op"      # the statistic that carries an operation's scope
# a collective by its short name: the synchronous ones, and the two halves
# of one the compiler made asynchronous (``async-collective-start.N`` begins
# it, ``async-collective-done.N`` waits for it)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|async-collective)(-start|-done)?(\.|\s|$)")
_HLO = re.compile(r"^%([\w.\-]+) = \(?(\w+\[[\d,]*\])?")


def label(text):
    """``%fusion.12 = (f32[4,512]{...}, ...) fusion(...)`` ->
    ``fusion.12 f32[4,512]``; any other name as it is."""
    m = _HLO.match(text)
    if not m:
        return text
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def find_xplane(log_dir):
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % log_dir)
    return paths[-1]


def load(path):
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    planes, detail, short = [], {}, {}
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                name = ev.name
                if device:
                    if name not in short:
                        short[name] = label(name)
                        detail.setdefault(short[name], name)
                    name = short[name]
                events.append((name, int(ev.start_ns), int(ev.duration_ns)))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    scope = {}
    for name, value in _metadata_stat(raw, SCOPE_STAT).items():
        if name in short:
            scope.setdefault(short[name], value)
    return {"planes": planes, "detail": detail, "scope": scope}


# -- the wire format, by hand (no protobuf module here) ---------------------
# XSpace.planes=1; XPlane id=1 name=2 lines=3 event_metadata=4 (map of id to
# XEventMetadata id=1 name=2 stats=5) stat_metadata=5 (map of id to
# XStatMetadata id=1 name=2); XLine id=1 name=2 timestamp_ns=3 events=4;
# XEvent metadata_id=1 offset_ps=2 duration_ps=3; XStat metadata_id=1
# str_value=5

def _read_varint(buf, i):
    n, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return n, i


def _fields(buf):
    """``(number, value)`` of each field of a message: an int for a varint,
    the bytes of any other."""
    i = 0
    while i < len(buf):
        key, i = _read_varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _read_varint(buf, i)
        else:
            if wire == 2:
                size, i = _read_varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError("wire type %d in an XSpace" % wire)
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def _metadata_stat(raw, stat):
    """``{event name: value}`` of the string statistic ``stat`` that the
    device planes' event metadata carry; ``{}`` where none does."""
    out = {}
    for num, plane in _fields(raw):
        if num != 1:
            continue
        name, events, ids = "", [], set()
        for n, v in _fields(plane):
            if n == 2:
                name = v.decode()
            elif n == 4:        # a map entry: key=1, value=2
                events.append(list(_fields(dict(_fields(v)).get(2, b""))))
            elif n == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                if meta.get(2, b"").decode() == stat:
                    ids.add(meta.get(1))
        if not ids or not DEVICE_PLANE.match(name):
            continue
        for meta in events:
            event = dict(meta).get(2, b"").decode()
            for n, v in meta:
                st = dict(_fields(v)) if n == 5 else {}
                if st.get(1) in ids and 5 in st:
                    out.setdefault(event, st[5].decode())
    return out


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, wire, payload):
    if wire == 0:
        return _varint(num << 3) + _varint(payload)
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def write_xspace(trace, path):
    """Write ``trace`` (``load``'s form: the ``planes``, and ``detail`` and
    ``scope`` where it has them) as an XSpace that ``load`` reads back."""
    space = b""
    scopes = trace.get("scope", {})
    for pi, plane in enumerate(trace["planes"], 1):
        ids, paths = {}, {}
        body = _field(1, 0, pi) + _field(2, 2, plane["name"].encode())
        for li, line in enumerate(plane["lines"], 1):
            t0 = min((s for _, s, _ in line["events"]), default=0)
            lb = _field(1, 0, li) + _field(2, 2, line["name"].encode()) \
                + _field(3, 0, t0)
            for name, start, dur in line["events"]:
                scope = scopes.get(name)
                name = trace.get("detail", {}).get(name, name)
                mid = ids.setdefault(name, len(ids) + 1)
                if scope is not None:
                    paths[mid] = scope
                lb += _field(4, 2, _field(1, 0, mid)
                             + _field(2, 0, (start - t0) * 1000)
                             + _field(3, 0, dur * 1000))
            body += _field(3, 2, lb)
        for name, mid in ids.items():
            meta = _field(1, 0, mid) + _field(2, 2, name.encode())
            if mid in paths:
                meta += _field(5, 2, _field(1, 0, 1)
                               + _field(5, 2, paths[mid].encode()))
            body += _field(4, 2, _field(1, 0, mid) + _field(2, 2, meta))
        if paths:       # stat_metadata 1 is the scope statistic's name
            body += _field(5, 2, _field(1, 0, 1) + _field(2, 2, _field(
                1, 0, 1) + _field(2, 2, SCOPE_STAT.encode())))
        space += _field(1, 2, body)
    with open(path, "wb") as f:
        f.write(space)


# -- reductions --------------------------------------------------------------

def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace):
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def device_ops(trace):
    """``{plane name: [(name, start_ns, duration_ns)]}`` of the devices
    that ran something."""
    out = {p["name"]: _line(p, OPS_LINE) for p in device_planes(trace)}
    return {k: v for k, v in out.items() if v}


def clip(events, t0, t1):
    """The parts of ``events`` inside ``[t0, t1)``."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union_ns(intervals):
    """Total length of the union of ``[(start, duration)]``."""
    total, end = 0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_seconds(trace, t0=None, t1=None):
    """Seconds in which an operation ran, averaged over the devices that
    ran something, and the number of such devices."""
    per = []
    for events in device_ops(trace).values():
        if t0 is not None:
            events = clip(events, t0, t1)
        per.append(union_ns([(s, d) for _, s, d in events]) / 1e9)
    if not per:
        return 0.0, 0
    return sum(per) / len(per), len(per)


def self_times(events):
    """``[(name, self_ns)]``: each event's duration less what the events
    nested inside it cover (a ``while`` holds its body's operations)."""
    out, stack = [], []      # stack of [name, end, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    out.extend((n, t) for n, _, t in stack)
    return out


def _family(name):
    """``fusion.123 f32[8]`` -> ``fusion``: instances of one kind."""
    return re.sub(r"[.\d]+$", "", name.split(" ")[0]) or name


def top_ops(trace, n=10, t0=None, t1=None, by=None):
    """``[[name, seconds]]``: the device operations that took most self
    time, per device (averaged over the devices): each instruction by its
    short name, or with ``by=_family`` each kind."""
    by = by or (lambda name: name)
    ops = device_ops(trace)
    totals = {}
    for events in ops.values():
        if t0 is not None:
            events = clip(events, t0, t1)
        for name, t in self_times(events):
            totals[by(name)] = totals.get(by(name), 0) + t
    k = max(1, len(ops))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / 1e9 / k] for name, t in ranked]


def _matching(trace, pattern, text_of, t0, t1):
    rx = re.compile(pattern)
    ops = device_ops(trace)
    total, count = 0, 0
    for events in ops.values():
        if t0 is not None:
            events = clip(events, t0, t1)
        n = 0
        for name, t in self_times(events):
            if rx.search(text_of(name)):
                total += t
                n += 1
        count = max(count, n)
    return total / 1e9 / max(1, len(ops)), count


def matching_seconds(trace, pattern, t0=None, t1=None, detail=False):
    """Summed self time, per device, of the operations whose short name
    (with ``detail`` their whole text) matches ``pattern``; and how many
    such events there were on the busiest device."""
    texts = trace.get("detail", {}) if detail else {}
    return _matching(trace, pattern, lambda n: texts.get(n, n), t0, t1)


def scope_seconds(trace, pattern, t0=None, t1=None):
    """The same for the operations whose scope (the path of ``jit`` and
    ``jax.named_scope`` names they were traced under) matches ``pattern``;
    an operation without one has the empty path."""
    scopes = trace.get("scope", {})
    return _matching(trace, pattern, lambda n: scopes.get(n, ""), t0, t1)


def collective_exposed_seconds(trace, t0=None, t1=None):
    """Seconds, per device, inside a collective during which nothing else
    ran on that device."""
    ops = device_ops(trace)
    total = 0
    for events in ops.values():
        if t0 is not None:
            events = clip(events, t0, t1)
        coll = [(s, d) for n, s, d in events if COLLECTIVE.match(n)]
        rest = [(s, d) for n, s, d in events if not COLLECTIVE.match(n)]
        total += union_ns(coll + rest) - union_ns(rest)
    return total / 1e9 / max(1, len(ops))


def module_durations(trace, pattern=None):
    """``{module name: [seconds]}`` over the devices' ``XLA Modules``."""
    rx = re.compile(pattern) if pattern else None
    out = {}
    for plane in device_planes(trace):
        for name, _, d in _line(plane, MODULES_LINE):
            if rx is None or rx.search(name):
                out.setdefault(_family(re.sub(r"\(\d+\)$", "", name)),
                               []).append(d / 1e9)
    return out


def marks(trace, prefix=MARK):
    """The benchmark's own host annotations, ``[(name, start, duration)]``
    sorted by start."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            out += [e for e in line["events"] if e[0].startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


def window_of(trace, name=MARK + "window"):
    """``(t0, t1)`` of the annotation that spans the measured window."""
    for n, s, d in marks(trace):
        if n == name:
            return s, s + d
    return None


def idle_gaps(trace, n=10, t0=None, t1=None):
    """``[[what the host was doing, seconds]]``: device idle time summed
    by the innermost ``bench:`` annotation that covers each gap's start
    (``host (unattributed)`` where none does), on the first device."""
    ops = device_ops(trace)
    if not ops:
        return []
    events = ops[sorted(ops)[0]]
    if t0 is None:
        t0 = min(s for _, s, _ in events)
        t1 = max(s + d for _, s, d in events)
    events = clip(events, t0, t1)
    spans = [m for m in marks(trace) if m[0] != MARK + "window"]
    gaps, end = [], t0
    for s, d in sorted((s, d) for _, s, d in events):
        if s > end:
            gaps.append((end, s - end))
        end = max(end, s + d)
    if t1 > end:
        gaps.append((end, t1 - end))
    totals = {}
    for gs, gd in gaps:
        cover = [m for m in spans if m[1] <= gs < m[1] + m[2]]
        what = min(cover, key=lambda m: m[2])[0][len(MARK):] if cover \
            else "host (unattributed)"
        totals[what] = totals.get(what, 0) + gd
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def summary(trace, names=40):
    """What a trace holds, for the look by hand: every plane and line with
    its event count, and the commonest names of each line with the stats
    of their first event."""
    out = []
    for plane in trace["planes"]:
        lines = []
        for ln in plane["lines"]:
            counts = {}
            for name, _, d in ln["events"]:
                c = counts.setdefault(name, [0, 0])
                c[0] += 1
                c[1] += d
            top = sorted(counts.items(), key=lambda kv: -kv[1][1])[:names]
            lines.append({"line": ln["name"], "events": len(ln["events"]),
                          "top": [{"name": n, "count": c, "ns": t,
                                   "text": trace.get("detail", {}).get(
                                       n, "")[:600]}
                                  for n, (c, t) in top]})
        out.append({"plane": plane["name"], "lines": lines})
    return out


def cut(trace, t0, t1, keep_planes=None):
    """A small copy holding only what lies in ``[t0, t1)``: for fixtures."""
    planes = []
    for plane in trace["planes"]:
        if keep_planes and not any(re.search(k, plane["name"])
                                   for k in keep_planes):
            continue
        lines = [{"name": ln["name"], "events": clip(ln["events"], t0, t1)}
                 for ln in plane["lines"]]
        planes.append({"name": plane["name"],
                       "lines": [ln for ln in lines if ln["events"]]})
    return {"planes": planes, "detail": trace.get("detail", {}),
            "scope": trace.get("scope", {})}
