"""From a profiler trace to numbers. The yardstick's own reduction:
every PR computes the same number in the same way.

Two halves. ``read_planes`` turns an ``.xplane.pb`` into plain lists
(it needs ``jax.profiler.ProfileData`` and nothing else of JAX);
everything after it is arithmetic on ``(name, start, duration)``
tuples in seconds, checked in the tests on hand-made cases and on a
recorded excerpt.

What a TPU trace looks like (looked at by hand, PR 25): one plane a
chip, ``/device:TPU:<n>``; its line ``XLA Ops`` holds every
operation the chip ran, serially, named by its whole HLO line, a
``while`` as one long event with its body's operations inside it;
``Async XLA Ops`` the copies that run beside them; ``XLA Modules``
one event a program run; ``Steps`` one a module run. The host's threads are
lines of the plane ``/host:CPU``, where the worker's
``yardstick.*`` annotations are found.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: what XLA calls its collectives, synchronous or split in two
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|ragged-all-to-all)"
    r"(-start|-done)?(\.\d+)?( |$)"
)
HOST_PREFIX = "yardstick."
TOP = 10


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    )))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _profile(path):
    import jax.profiler

    return jax.profiler.ProfileData.from_file(path)


def short_name(text):
    """An operation's event is named by its whole HLO line,
    ``%fusion.3 = bf16[3,4096]{1,0:T(8,128)} fusion(...), kind=...``:
    keep the name and the result's shape, ``fusion.3 bf16[3,4096]``
    (a tuple's first member, with ``(`` before it)."""
    name, _, rest = text.partition(" = ")
    shape = re.match(r"\(?\w+\[[\d,]*\]", rest)
    return (name.lstrip("%") + (" " + shape.group() if shape else ""))


def read_planes(path):
    """``{"devices": {plane: [(name, start, dur), ...]}, "host":
    [(name, start, dur), ...]}``: each chip's operations, and the
    worker's own annotations, in seconds on the trace's clock."""
    devices, host = {}, []
    for plane in _profile(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (short_name(e.name), e.start_ns / 1e9,
                         e.duration_ns / 1e9)
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [
                    (e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX)
                ]
    return {"devices": devices, "host": host}


def describe(path, top=25):
    """A trace for the eye: planes, lines, and each line's names by
    total time. Where the first look at a new device starts."""
    out = []
    for plane in _profile(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            totals = {}
            for e in events:
                n, t = totals.get(e.name, (0, 0.0))
                totals[e.name] = (n + 1, t + e.duration_ns / 1e9)
            ranked = sorted(totals.items(), key=lambda kv: -kv[1][1])
            for name, (n, t) in ranked[:top]:
                out.append(f"    {t:10.6f}s {n:6d}x {name[:150]}")
            if events:
                out.append(
                    f"    one event's stats: "
                    f"{dict(events[len(events) // 2].stats)}"
                )
    return "\n".join(out) + "\n"


# -- arithmetic on (name, start, duration) ------------------------------

def leaves(events):
    """The events that hold no other event: a ``while`` or a call
    spans its body's operations, and counting both counts twice.
    Events of no length (markers) are dropped: they hold no time and
    would make a parent of the operation they start with."""
    ordered = sorted(
        (e for e in events if e[2] > 0), key=lambda e: (e[1], -e[2])
    )
    out = []
    for i, (name, start, dur) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[1] < start + dur and (
            nxt[1] + nxt[2] <= start + dur + 1e-12
        ):
            continue  # the next event lies inside this one
        out.append((name, start, dur))
    return out


def union(intervals):
    """Merged, sorted ``(start, end)`` pairs."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """The parts of ``intervals`` (merged) outside ``holes``
    (merged)."""
    out = []
    for a, b in intervals:
        for h0, h1 in holes:
            if h1 <= a or h0 >= b:
                continue
            if h0 > a:
                out.append((a, h0))
            a = max(a, h1)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def is_collective(name):
    return bool(COLLECTIVE.match(name))


def device_numbers(events):
    """One chip's operations into its window, busy time, idle gaps,
    time by name, and the time in which a collective ran and no
    compute did."""
    ops = leaves(events)
    spans = [(s, s + d) for _, s, d in ops]
    busy = union(spans)
    window = (busy[0][0], busy[-1][1])
    by_name = {}
    for name, _, dur in ops:
        n, t = by_name.get(name, (0, 0.0))
        by_name[name] = (n + 1, t + dur)
    collective = union(
        (s, s + d) for n, s, d in ops if is_collective(n)
    )
    compute = union(
        (s, s + d) for n, s, d in ops if not is_collective(n)
    )
    return {
        "window": window,
        "busy_s": total(busy),
        "gaps": subtract([window], busy),
        "by_name": by_name,
        "collective_exposed_s": total(subtract(collective, compute)),
    }


def label_gap(gap, host):
    """What the host was doing in a device's idle gap: the worker's
    annotation that covers most of it. The step annotation holds the
    others, so it names a gap only where none of them reaches it."""

    def cover(event):
        _, start, dur = event
        return min(gap[1], start + dur) - max(gap[0], start)

    inner = [e for e in host if e[0] != HOST_PREFIX + "step"]
    for candidates in (inner, host):
        covering = [e for e in candidates if cover(e) > 0]
        if covering:
            return max(covering, key=cover)[0]
    return "host:unannotated"


def reduce(planes, steps):
    """The numbers of one traced window of ``steps`` steps, or None
    where the trace shows no device. Times are seconds over the whole
    window, means over the chips; a reader divides by ``steps``."""
    per_device = [
        device_numbers(events)
        for _, events in sorted(planes["devices"].items()) if events
    ]
    if not per_device:
        return None
    k = len(per_device)
    names = {}
    for dev in per_device:
        for name, (n, t) in dev["by_name"].items():
            cn, ct = names.get(name, (0, 0.0))
            names[name] = (cn + n, ct + t)
    ops = sorted(
        ([name, t / k, n // k] for name, (n, t) in names.items()),
        key=lambda row: -row[1],
    )
    host = sorted(planes["host"], key=lambda e: (e[1], -e[2]))
    gaps = {}
    for gap in per_device[0]["gaps"]:
        label = label_gap(gap, host)
        gaps[label] = gaps.get(label, 0.0) + (gap[1] - gap[0])
    return {
        "steps": steps,
        "devices": k,
        "window_s": sum(
            d["window"][1] - d["window"][0] for d in per_device
        ) / k,
        "busy_s": sum(d["busy_s"] for d in per_device) / k,
        "collective_exposed_s": sum(
            d["collective_exposed_s"] for d in per_device
        ) / k,
        "ops": ops[:200],
        "idle_gaps": sorted(
            ([name, t] for name, t in gaps.items()),
            key=lambda row: -row[1],
        )[:TOP],
    }


def reduce_file(path, steps):
    return reduce(read_planes(path), steps)
