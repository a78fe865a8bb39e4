"""From the profiler's trace to device busy time, operations by name,
collectives and idle gaps. The only reduction there is: every PR's numbers
go through this file.

Two steps, so that the arithmetic can be checked on a small recorded trace
kept as JSON beside the tests:

    load_xplane(path)  ->  {"planes": [{"name", "lines": [{"name",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}
    reduce_trace(trace, window_name)  ->  the numbers

What a TPU trace looks like (looked at by hand on a v5e, PR 22): one plane
``/device:TPU:<n>`` per chip; its line ``XLA Ops`` holds one event per
executed HLO instruction under the name XLA gave it, control flow
(``while``) as a long event around its body; ``XLA Modules`` holds one
event per executed program; ``/host:CPU`` holds one line per host thread
with ``TraceAnnotation`` spans and the runtime's own.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: HLO instruction names of collectives (sync, or the start/done of async)
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|ragged-all-to-all)")
MIN_GAP_NS = 20_000
_OPCODE = re.compile(r"(?:^|[\s)}])([a-z][a-z0-9_\-]*)\(")


def display_name(text):
    """A device event is named by its whole HLO instruction (``%fusion.181
    = bf16[16,128,14336]{...} fusion(...)``, kilobytes for a kernel with
    many operands). Kept: ``<name> <opcode> <result shape>``, and for a
    custom call its target: ``closed_call.14 custom-call(tpu_custom_call)
    bf16[16,8,512,128]`` is a Mosaic (Pallas) kernel."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    found = _OPCODE.search(rest)
    opcode = found.group(1) if found else "?"
    if opcode == "custom-call":
        target = re.search(r'custom_call_target=\\?"([\w.$\-]+)', rest)
        opcode += f"({target.group(1)})" if target else ""
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return " ".join(filter(None, [name.lstrip("%"), opcode,
                                  shape.group(1) if shape else ""]))


def opcode_of(name):
    """The opcode of a display name; a bare name (``all-reduce.2``) is its
    own."""
    parts = name.split(" ")
    return parts[1] if len(parts) > 1 else parts[0]


def is_collective(name):
    return bool(COLLECTIVE.match(opcode_of(name)))


def is_custom_call(name):
    return opcode_of(name).startswith("custom-call")


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path):
    """Read an ``.xplane.pb`` with JAX alone. The python tracer's frames
    (``$file:line``) are dropped."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [[display_name(ev.name), int(ev.start_ns),
                       int(ev.duration_ns)]
                      for ev in line.events if not ev.name.startswith("$")]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_newest(trace_dir):
    """The newest trace under ``trace_dir``, or None."""
    path = newest_xplane(trace_dir)
    return None if path is None else load_xplane(path)


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals):
    return sum(b - a for a, b in intervals)


def self_times(events):
    """Each event's duration minus what its children cover (events nest as
    a control-flow op nests its body). Returns [(name, start, self_ns)]."""
    out, stack = [], []      # stack of [name, start, end, child_ns]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, end, child = stack.pop()
            out.append((name, start, max(0, end - start - child)))
            if stack:
                stack[-1][3] += end - start

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, start, start + dur, 0])
    close(float("inf"))
    return out


def _ops_line(plane):
    for line in plane["lines"]:
        if line["name"] == OPS_LINE:
            return line
    return None


def find_spans(trace, prefix):
    """Host annotations whose name starts with ``prefix``, as sorted
    (start, end, name)."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(prefix):
                    out.append((start, start + dur, name))
    return sorted(out)


def find_span(trace, name):
    """[start, end) of the host annotation called ``name``, or None."""
    found = [s for s in find_spans(trace, name) if s[2] == name]
    return list(found[0][:2]) if found else None


def host_spans(trace, lo, hi):
    """Spans of the python threads (``TraceAnnotation``s and JAX's own
    python-level spans: the program's host code) that touch [lo, hi), as
    (name, start, end). The runtime's and the compiler's worker threads are
    left out: their pass names say what a thread did, not what the program
    waited for."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            if not line["name"].startswith("python"):
                continue
            for name, start, dur in line["events"]:
                if dur > 0 and start < hi and start + dur > lo:
                    out.append((name, start, start + dur))
    return out


def attribute_gaps(gaps, spans, window_name):
    """Idle seconds by what the host was doing. Each gap is named by the
    outermost and the innermost host span that cover its middle
    (``serve_frame/w1/s8>np.asarray(jax.Array)``; one name if they are the
    same), and ``unattributed-host`` where no span does."""
    spans = [s for s in spans if s[0] != window_name]
    by_name = {}
    for a, b in gaps:
        mid = (a + b) / 2
        covering = sorted((s for s in spans if s[1] <= mid < s[2]),
                          key=lambda s: s[1] - s[2])
        if not covering:
            name = "unattributed-host"
        elif covering[0][0] == covering[-1][0]:
            name = covering[0][0]
        else:
            name = f"{covering[0][0]}>{covering[-1][0]}"
        by_name[name] = by_name.get(name, 0) + (b - a)
    return by_name


def reduce_trace(trace, window_name=None, window=None, top=10):
    """The numbers of one trace. The window is ``window`` ([lo, hi) in the
    trace's nanoseconds) if given, else the host annotation ``window_name``
    if the trace has it, else first to last device event. Returns None if
    no operation ran on a device."""
    devices = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            line = _ops_line(plane)
            if line and line["events"]:
                devices.append((plane["name"], line["events"]))
    if not devices:
        return None
    if window is None and window_name:
        window = find_span(trace, window_name)
    if window is None:
        window = [min(e[1] for _, evs in devices for e in evs),
                  max(e[1] + e[2] for _, evs in devices for e in evs)]
    lo, hi = window
    n = len(devices)
    busy_ns, op_ns, gap_ns = 0, {}, {}
    collective_ns = custom_ns = 0
    spans = host_spans(trace, lo, hi)
    for _, events in devices:
        events = [e for e in events if e[1] < hi and e[1] + e[2] > lo]
        busy = clip(union([e[1], e[1] + e[2]] for e in events), lo, hi)
        busy_ns += total(busy)
        for name, start, self_ns in self_times(events):
            if not lo <= start < hi:
                continue
            op_ns[name] = op_ns.get(name, 0) + self_ns
            if is_collective(name):
                collective_ns += self_ns
            elif is_custom_call(name):
                custom_ns += self_ns
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                if edges[i + 1] - edges[i] >= MIN_GAP_NS]
        for name, ns in attribute_gaps(gaps, spans, window_name).items():
            gap_ns[name] = gap_ns.get(name, 0) + ns

    def ranked(by_name):
        return [[name, ns / n / 1e9] for name, ns in
                sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / n / 1e9,
            "devices": n,
            "collective_exposed_s": collective_ns / n / 1e9,
            "custom_call_s": custom_ns / n / 1e9,
            "device_ops": ranked(op_ns), "idle_gaps": ranked(gap_ns)}
