"""Reader and reduction for the profiler's ``.xplane.pb``, in plain Python.

The benchmark's parent never imports JAX, so it reads the protobuf wire
format itself. Only what the reduction needs is decoded (tensorflow's
``xplane.proto``):

    XSpace          1: planes (XPlane)
    XPlane          2: name   3: lines (XLine)   4: event_metadata (map)
    XLine           2: name   3: timestamp_ns    4: events (XEvent)
                    11: display_name
    XEvent          1: metadata_id   2: offset_ps   3: duration_ps
    map entry       1: key    2: value (XEventMetadata)
    XEventMetadata  1: id     2: name   4: display_name

A plane whose name does not start with the wanted prefix is skipped
without being decoded: the host planes hold most of a trace's bytes.
"""
import glob
import os


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """Yield (field number, wire type, value) of one message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 1:
            val = bytes(buf[i:i + 8])
            i += 8
        elif wt == 5:
            val = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, wt, val


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _metadata_names(plane_buf):
    names = {}
    for num, _, val in fields(plane_buf):
        if num != 4:
            continue
        key, name = None, ""
        for n2, _, v2 in fields(val):
            if n2 == 1:
                key = v2
            elif n2 == 2:
                for n3, _, v3 in fields(v2):
                    if n3 == 2:
                        name = _text(v3)
        names[key] = name
    return names


def read_planes(path, prefix="/device:"):
    """[{"name", "lines": [{"name", "events": [(name, start_ps,
    duration_ps)]}]}] for the planes whose name starts with ``prefix``.
    Starts are picoseconds on the trace's own clock."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, _, plane in fields(space):
        if num != 1:
            continue
        name = next((_text(v) for n, _, v in fields(plane) if n == 2), "")
        if not name.startswith(prefix):
            continue
        meta = _metadata_names(plane)
        lines = []
        for n, _, line in fields(plane):
            if n != 3:
                continue
            lname, t0_ns, events = "", 0, []
            for n2, _, v2 in fields(line):
                if n2 == 2:
                    lname = _text(v2)
                elif n2 == 11 and not lname:
                    lname = _text(v2)
                elif n2 == 3:
                    t0_ns = v2
                elif n2 == 4:
                    mid = off = dur = 0
                    for n3, _, v3 in fields(v2):
                        if n3 == 1:
                            mid = v3
                        elif n3 == 2:
                            off = v3
                        elif n3 == 3:
                            dur = v3
                    events.append((mid, off, dur))
            lines.append({"name": lname, "events": [
                (meta.get(m, str(m)), t0_ns * 1000 + off, dur)
                for m, off, dur in events]})
        planes.append({"name": name, "lines": lines})
    return planes


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a profiler output directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def union_ps(intervals):
    """Total length of the union of (start, duration) intervals."""
    total, end = 0, None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def reduce_device(planes):
    """Reduce device planes to what the metrics read.

    busy_s: per chip, the union of the intervals of its ``XLA Ops`` line
    (every operation that ran on the device), averaged over the chips
    that have such a line. modules: {program name: [seconds, launches]}
    from the ``XLA Modules`` lines, summed over chips. ops: the same by
    operation name. launches: every program launch of every chip as
    (start_ps, duration_ps). gaps_by_next: the idle time by the program
    that ended each gap. Returns None when no plane has an operation."""
    busy, modules, ops, launches = [], {}, {}, []
    for p in planes:
        op_iv = []
        for ln in p["lines"]:
            is_ops = ln["name"] == "XLA Ops"
            if not (is_ops or ln["name"] == "XLA Modules"):
                continue
            for name, start, dur in ln["events"]:
                if is_ops:
                    op_iv.append((start, dur))
                    row = ops.setdefault(name, [0.0, 0])
                else:
                    launches.append((start, dur))
                    row = modules.setdefault(name, [0.0, 0])
                row[0] += dur / 1e12
                row[1] += 1
        if op_iv:
            busy.append(union_ps(op_iv) / 1e12)
    if not busy:
        return None
    first, last = span_ps(planes, ("XLA Ops", "XLA Modules"))
    return {"busy_s": sum(busy) / len(busy), "chips": len(busy),
            "window_s": (last - first) / 1e12, "span_ps": (first, last),
            "modules": modules, "ops": ops, "launches": sorted(launches),
            "gaps_by_next": gaps_by_next(planes)}


def span_ps(planes, line_names=None):
    """(first start, last end) over the device planes' events."""
    evs = [(s, s + d) for p in planes for ln in p["lines"]
           if line_names is None or ln["name"] in line_names
           for _, s, d in ln["events"]]
    return (min(e[0] for e in evs), max(e[1] for e in evs)) if evs else None


def gaps_by_next(planes):
    """The device's idle time by the program whose launch ended each
    gap: [(program, seconds)], longest first, from the chip with the
    most program launches. What the host was doing in a gap is getting
    that program ready: the name says which."""
    best = []
    for p in planes:
        for ln in p["lines"]:
            if ln["name"] == "XLA Modules" and len(ln["events"]) > len(best):
                best = ln["events"]
    out, end = {}, None
    for name, start, dur in sorted(best, key=lambda e: e[1]):
        if end is not None and start > end:
            out[name] = out.get(name, 0.0) + (start - end) / 1e12
        end = max(end or 0, start + dur)
    return sorted(out.items(), key=lambda kv: -kv[1])
