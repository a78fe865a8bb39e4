"""From the profiler's trace to device time by layer scope and by kernel
name, idle time by the program's own spans, and each frame's work: the
reduction behind the per-layer metrics that read what the program names
(``jax.named_scope``, ``pl.pallas_call(name=)``, ``telemetry.phase``).

What a TPU trace holds beyond ``trace_reduce``'s reading (looked at by hand
on a v5e, PR 23): an ``XLA Ops`` event's name is its HLO instruction with
no metadata, and its own stats are three clock values. The op's JAX path
(``jit(loop)/while/body/closed_call/paged_attn/paged_attn_c1/pallas_call:``:
jit, control flow, named scopes, kernel name, primitive) is the stat
``tf_op`` of the event's METADATA record in the plane, which
``jax.profiler.ProfileData`` does not expose: ``event_paths`` reads those
records from the file's wire format. A Pallas kernel's ``name=`` is also its
HLO instruction's name (``%paged_attn_c1.3 = ... custom-call``). XLA's own
layout copies of a whole KV pool carry no path (one reads ``pool:``, the
parameter's name): a ``copy`` whose result has the pool's shape is counted
under ``kv_commit``. ``jax.checkpoint`` writes ``checkpoint/`` into the path
of what it wraps and ``checkpoint/rematted_computation/`` into the path of
what the backward pass computes again. A host ``TraceAnnotation``'s keyword
arguments are the stats of its event, which ``ProfileData`` does expose:
``serve/frame_work`` carries each frame's counters that way.

Loaded once per process from the newest ``.xplane.pb`` under
``harness.RUN_DIR/*/trace``; arithmetic shared with ``trace_reduce`` by
import.
"""

import glob
import os
import re

from perfbench import harness, trace_reduce

#: layer scopes of the program, serving and training; an op belongs to the
#: innermost one on its path
SCOPES = frozenset((
    "embed", "attn", "attn_qkv", "paged_attn", "attn_out", "mlp", "moe_mlp",
    "kv_commit", "lm_head", "lm_head_loss", "frame_plan", "sample",
    "optimizer", "zero_gather", "zero_reduce_scatter"))
UNSCOPED = "unscoped"
#: the program's own host spans: an idle gap under one of them has a name
PROGRAM_SPANS = ("serve/", "serve_frame/", "train/", "train_batch")
UNATTRIBUTED = "unattributed-host"
#: the serve loop's poll on an empty server: the wait for an arrival, which
#: is neither the boundary's work nor an unnamed gap
EMPTY_SERVER = "serve/idle"
#: phases that are no host work between two frames: the host waiting for
#: the chip, and for a request
WAITS = ("fetch", "idle")
WINDOW_SPAN = "perfbench/trace_window"
FRAME_SPAN, FRAME_WORK, STEP_SPAN = "serve_frame/", "serve/frame_work", \
    "train_batch"
REMAT = "rematted_computation"
_WRAPPED = re.compile(r"^(?:\w+\()+|\)+$")


# ---------------------------------------------------------------------------
# the file: event metadata by hand, events through jax
# ---------------------------------------------------------------------------


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def event_paths(path):
    """{plane name: {event name: op path}} from the ``tf_op`` stat of each
    plane's event metadata (XSpace.planes=1; XPlane.name=2,
    .event_metadata=4, .stat_metadata=5; map entries key=1, value=2;
    XEventMetadata.name=2, .stats=5; XStat.metadata_id=1, .str_value=5;
    XStatMetadata.id=1, .name=2). The planes' lines, which hold the bulk
    of the file, are stepped over."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, tf_op = None, [], None
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4:
                events.append(value)
            elif field == 5:
                stat = dict(_fields(dict(_fields(value))[2]))
                if bytes(stat.get(2, b"")) == b"tf_op":
                    tf_op = stat[1]
        if tf_op is None or not trace_reduce.DEVICE_PLANE.match(name or ""):
            continue
        paths = out.setdefault(name, {})
        for entry in events:
            event_name, op = None, None
            for field, value in _fields(dict(_fields(entry))[2]):
                if field == 2:
                    event_name = bytes(value).decode()
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) == tf_op and 5 in stat:
                        op = bytes(stat[5]).decode()
            if event_name and op:
                paths[event_name] = op
    return out


def load_scoped(path):
    """``trace_reduce.load_xplane``'s structure, with a fourth element on
    every ``XLA Ops`` event, its op path ("" where XLA gives none), and the
    frames' work beside the planes: ``{"planes": [...], "frame_work":
    [[start_ns, {counter: value}], ...]}``."""
    from jax.profiler import ProfileData
    paths = event_paths(path)
    planes, work = [], []
    for plane in ProfileData.from_file(path).planes:
        by_name = paths.get(plane.name, {})
        lines = []
        for line in plane.lines:
            scoped = (line.name == trace_reduce.OPS_LINE
                      and trace_reduce.DEVICE_PLANE.match(plane.name))
            events = []
            for ev in line.events:
                if ev.name.startswith("$"):
                    continue
                row = [trace_reduce.display_name(ev.name), int(ev.start_ns),
                       int(ev.duration_ns)]
                if scoped:
                    row.append(by_name.get(ev.name, ""))
                elif ev.name == FRAME_WORK:
                    work.append([int(ev.start_ns),
                                 {k: int(v) for k, v in ev.stats}])
                events.append(row)
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "frame_work": sorted(work)}


# ---------------------------------------------------------------------------
# one op -> its scope, its kernel
# ---------------------------------------------------------------------------


def scope_of(name, path, pool_shape=None):
    """The innermost scope of ``SCOPES`` on the op's path (autodiff wraps
    a scope it differentiates: ``transpose(jvp(mlp))`` is ``mlp``); a
    ``copy`` whose result has the KV pool's shape is ``kv_commit``;
    otherwise ``unscoped``."""
    for part in reversed(path.rstrip(":").split("/")):
        part = _WRAPPED.sub("", part)
        if part in SCOPES:
            return part
    if pool_shape and trace_reduce.opcode_of(name) == "copy" \
            and re.search(pool_shape, name):
        return "kv_commit"
    return UNSCOPED


def pool_shape_pattern(config, kv_blocks):
    """The KV pools' shape ``[layers, kv_heads, pages, page, head_dim]`` as
    a pattern over a display name's result shape (any page size)."""
    heads = config["num_attention_heads"]
    kvh = config.get("num_key_value_heads") or heads
    d = config.get("head_dim") or config["hidden_size"] // heads
    return (rf"\[{config['num_hidden_layers']},{kvh},{int(kv_blocks)},"
            rf"\d+,{d}\]")


def kernel_of(name):
    """The name a Mosaic kernel was given (``paged_attn_c1.3
    custom-call(tpu_custom_call) ...`` -> ``paged_attn_c1``), or None for
    any other op."""
    if "custom-call(tpu_custom_call)" not in name:
        return None
    return re.sub(r"\.\d+$", "", name.split(" ", 1)[0])


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def reduce_scoped(trace, lo, hi, pool_shape=None):
    """Over [lo, hi) of the trace's clock, mean over chips: device busy
    seconds; self seconds by scope (``unscoped`` among them; they sum to
    busy), by kernel name and of recomputed ops; idle seconds by the
    program span over each gap's middle (every gap, not the longest ten).
    None if no operation ran on a device."""
    devices = []
    for plane in trace["planes"]:
        if trace_reduce.DEVICE_PLANE.match(plane["name"]):
            for line in plane["lines"]:
                if line["name"] == trace_reduce.OPS_LINE and line["events"]:
                    devices.append(line["events"])
    if not devices:
        return None
    spans = [s for s in trace_reduce.host_spans(trace, lo, hi)
             if s[0].startswith(PROGRAM_SPANS)]
    # the program's spans nest (serve_frame > serve/fetch, serve/retire >
    # serve/yield): a span's self time is what the host spent in it alone
    span_ns = {}
    for name, start, self_ns in trace_reduce.self_times(
            [[s[0], s[1], s[2] - s[1]] for s in spans]):
        if lo <= start < hi:
            span_ns[name] = span_ns.get(name, 0) + self_ns
    n = len(devices)
    busy_ns = remat_ns = 0
    scope_ns, kernel_ns, gap_ns, loose_ns = {}, {}, {}, {}
    for events in devices:
        events = [e for e in events if e[1] < hi and e[1] + e[2] > lo]
        busy = trace_reduce.clip(
            trace_reduce.union([e[1], e[1] + e[2]] for e in events), lo, hi)
        busy_ns += trace_reduce.total(busy)
        keyed = [((e[0], e[3] if len(e) > 3 else ""), e[1], e[2])
                 for e in events]
        for (name, path), start, self_ns in trace_reduce.self_times(keyed):
            if not lo <= start < hi:
                continue
            scope = scope_of(name, path, pool_shape)
            scope_ns[scope] = scope_ns.get(scope, 0) + self_ns
            if scope == UNSCOPED:
                loose_ns[name] = loose_ns.get(name, 0) + self_ns
            kernel = kernel_of(name)
            if kernel:
                kernel_ns[kernel] = kernel_ns.get(kernel, 0) + self_ns
            if REMAT in path:
                remat_ns += self_ns
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                if edges[i + 1] - edges[i] >= trace_reduce.MIN_GAP_NS]
        for name, ns in trace_reduce.attribute_gaps(gaps, spans,
                                                    None).items():
            gap_ns[name] = gap_ns.get(name, 0) + ns

    def seconds(by_name):
        return {k: v / n / 1e9 for k, v in
                sorted(by_name.items(), key=lambda kv: -kv[1])}

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / n / 1e9,
            "devices": n, "scope_s": seconds(scope_ns),
            "kernel_s": seconds(kernel_ns), "remat_s": remat_ns / n / 1e9,
            "idle_s": sum(gap_ns.values()) / n / 1e9,
            "idle_by_span": seconds(gap_ns),
            "empty_s": sum(v for k, v in gap_ns.items()
                           if k.startswith(EMPTY_SERVER)) / n / 1e9,
            "host_s": {k: v / 1e9 for k, v in sorted(span_ns.items())},
            "unscoped_ops": list(seconds(loose_ns).items())[:5]}


def frames_with_work(trace, lo, hi):
    """The whole ``serve_frame/`` spans inside [lo, hi], each with the work
    the program wrote for it: the first ``serve/frame_work`` after the
    frame's end and before the next frame's. Stops at the first frame
    whose work the trace does not hold (it ended first)."""
    frames = [s for s in trace_reduce.find_spans(trace, FRAME_SPAN)
              if s[0] >= lo and s[1] <= hi]
    work = trace.get("frame_work") or []
    out = []
    for i, (start, end, name) in enumerate(frames):
        nxt = frames[i + 1][0] if i + 1 < len(frames) else float("inf")
        found = [w for t, w in work if end <= t < nxt]
        if not found:
            break
        out.append((start, end, name, found[0]))
    return out


def serve_reduction(trace, config, kv_blocks):
    """The traced frames of a serving run: device time by scope and kernel
    over the whole frames that have their work in the trace, and that work
    summed by frame width."""
    window = trace_reduce.find_span(trace, WINDOW_SPAN)
    if window is None:
        return None
    frames = frames_with_work(trace, *window)
    if not frames:
        return None
    red = reduce_scoped(trace, frames[0][0], frames[-1][1],
                        pool_shape_pattern(config, kv_blocks))
    if red is None:
        return None
    red["frames"] = len(frames)
    for split in ("narrow", "wide"):
        rows = [w for *_, w in frames
                if (w["width"] > 1) == (split == "wide")]
        red[f"frames_{split}"] = len(rows)
        for key in ("kv_positions_read", "attn_pairs"):
            red[f"{key}_{split}"] = sum(w[key] for w in rows)
    return red


def train_reduction(trace):
    """The traced steps of a training run: from the first ``train_batch``
    step to the end of the traced window (every step is waited for inside
    it, so the steps are whole)."""
    steps = [s for s in trace_reduce.find_spans(trace, STEP_SPAN)
             if s[2] == STEP_SPAN]
    window = trace_reduce.find_span(trace, WINDOW_SPAN)
    if window is not None:
        steps = [s for s in steps if s[0] >= window[0] and s[1] <= window[1]]
    if not steps:
        return None
    red = reduce_scoped(trace, steps[0][0],
                        window[1] if window else steps[-1][1])
    if red is not None:
        red["steps"] = len(steps)
    return red


# ---------------------------------------------------------------------------
# for the readers: the run's newest trace, reduced once
# ---------------------------------------------------------------------------


def newest_trace():
    found = [p for d in glob.glob(os.path.join(harness.RUN_DIR, "*", "trace"))
             for p in [trace_reduce.newest_xplane(d)] if p]
    return max(found, key=os.path.getmtime) if found else None


_REDUCED = {}


def for_ctx(ctx):
    """The run's reduction, or None: where the driver read no trace, where
    the trace holds none of the program's spans (a program older than
    they are), or where nothing ran on a device. Logged once."""
    if not ctx or not ctx.get("trace"):
        return None
    path = newest_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path), ctx.get("kind"))
    if key not in _REDUCED:
        trace = load_scoped(path)
        if ctx.get("kind") == "train":
            red = train_reduction(trace)
        else:
            red = serve_reduction(trace, ctx["config"], ctx["kv_blocks"])
        if red is not None:
            log_tables(red, ctx)
        _REDUCED[key] = red
    return _REDUCED[key]


def share(part, whole):
    return 100.0 * part / whole if whole else None


def phase_ms_per_frame(counters):
    """{phase: host milliseconds per frame} from the ``host_<phase>_ns``
    counters over the window."""
    frames = counters.get("frames")
    if not frames:
        return {}
    return {k[len("host_"):-len("_ns")]: v / 1e6 / frames
            for k, v in counters.items()
            if k.startswith("host_") and k.endswith("_ns")}


def log_tables(red, ctx):
    """Device seconds by scope and by kernel, idle seconds by span, host
    milliseconds per frame by phase: what PERF.md section 5 is written
    from."""
    def table(by_name):
        return ", ".join(f"{k} {v:.3f}" for k, v in by_name.items()) or "none"

    harness.log(f"traced {red['window_s']:.2f} s: busy {red['busy_s']:.3f} s "
                f"by scope: {table(red['scope_s'])} (sum "
                f"{sum(red['scope_s'].values()):.3f})")
    harness.log(f"by kernel: {table(red['kernel_s'])}; recomputed "
                f"{red['remat_s']:.3f} s")
    if red["unscoped_ops"]:
        harness.log(f"largest unscoped ops: {table(dict(red['unscoped_ops']))}")
    harness.log(f"idle {red['idle_s']:.3f} s by span: "
                f"{table(red['idle_by_span'])}; {red['empty_s']:.3f} s of it "
                f"on an empty server ({EMPTY_SERVER})")
    if not red.get("frames"):
        return
    per_frame = 1e3 / red["frames"]
    traced = {k[len("serve/"):]: v * per_frame
              for k, v in red["host_s"].items()
              if k.startswith("serve/") and k != FRAME_WORK}
    host = sum(v for k, v in traced.items() if k not in WAITS)
    harness.log(
        "host ms per traced frame by phase (the spans' self times): "
        + ", ".join(f"{k} {v:.2f}" for k, v in traced.items())
        + f"; without {' and '.join(WAITS)} {host:.2f} against idle with "
        f"requests live {(red['idle_s'] - red['empty_s']) * per_frame:.2f}")
    counters = ctx.get("counters") or {}
    phases = phase_ms_per_frame(counters)
    harness.log("host ms per frame by phase (counters, whole window): "
                + ", ".join(f"{k} {v:.2f}" for k, v in phases.items()))
    # the counters' useful_position_share against the monitor's frames: a
    # frame that consumed prompt tokens is one prefill chunk wide
    frames, serve = ctx.get("frames"), ctx["config"].get("serve") or {}
    if frames and counters.get("positions_computed") and serve.get("batch"):
        chunk = int(serve.get("prefill_chunk_size", 128))
        computed = sum(serve["batch"] * (chunk if f[2] > 0 else 1) * f[3]
                       for f in frames)
        useful = counters["prefill_tokens"] + counters["target_forwards"]
        harness.log(
            f"useful positions: counters {useful} of "
            f"{counters['positions_computed']} "
            f"({share(useful, counters['positions_computed']):.2f}%); the "
            f"monitor's frames {sum(f[1] + f[2] for f in frames)} prompt + "
            f"generated tokens of {computed} "
            f"({share(sum(f[1] + f[2] for f in frames), computed):.2f}%)")
