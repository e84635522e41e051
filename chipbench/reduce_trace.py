"""From the profiler's ``.xplane.pb`` to numbers: device busy time, the
window, time per device operation, and the idle gaps by what the host was
doing.  Reads the file with ``jax.profiler.ProfileData`` and nothing else.

- A *device plane* is named ``/device:TPU:<n>``.  Its line ``XLA Ops`` holds
  one event per executed operation; where a plane has no such line every
  line but the step and module summaries counts.
- *Busy* is the union of the device events' intervals, averaged over device
  planes.  Time per operation is *self* time: a ``while`` or a call that
  encloses other operations on the same line is charged only what its
  children leave.  An operation is named by the token before `` = `` of its
  HLO text (``%fusion.12``), without the ``%``.  The *window* runs from the start of the first harness span to the
  end of the last (``chipbench.*`` events, written by ``Run.span`` through
  ``TraceAnnotation``), or over the device events where there is none.
- An *idle gap* is a stretch of the window in which no operation ran on the
  first device; it is booked to the innermost harness span that covers its
  middle, or to ``unattributed``.
"""

from __future__ import annotations

import glob
import os

_SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                  "Framework Name Scope", "Source code")


def find_xplane(trace_dir: str):
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    return files[-1] if files else None


def _events(line):
    return [
        (float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name)
        for e in line.events
    ]


def _union(intervals):
    """Merged, sorted intervals."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def short_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%")[:60]


def self_times(events, lo, hi):
    """name -> self time within [lo, hi] of properly nested events."""
    out, stack = {}, []  # stack of [end, name, self]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        close(a)
        if stack:
            stack[-1][2] -= b - a
        stack.append([b, short_name(name), b - a])
    close(float("inf"))
    return out


def _clip(intervals, lo, hi):
    return [
        (max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi
    ]


def device_ops(plane):
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == "XLA Ops"]
    if not ops:
        ops = [ln for ln in lines if ln.name not in _SUMMARY_LINES]
    return [ev for ln in ops for ev in _events(ln)]


def reduce_planes(planes, span_names=()):
    """``planes``: objects with ``.name`` and ``.lines`` (each line ``.name``
    and ``.events`` with ``start_ns``, ``duration_ns``, ``name``)."""
    wanted = {"chipbench." + n for n in span_names}
    device, spans = [], []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            device.append(device_ops(plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [
                    ev for ev in _events(line)
                    if ev[2] in wanted
                    or (not wanted and ev[2].startswith("chipbench."))
                ]
    device = [d for d in device if d]
    if not device:
        return None
    if spans:
        lo = min(s[0] for s in spans)
        hi = max(s[1] for s in spans)
    else:
        lo = min(e[0] for d in device for e in d)
        hi = max(e[1] for d in device for e in d)
    busy, per_op = [], {}
    for events in device:
        merged = _clip(_union([(a, b) for a, b, _ in events]), lo, hi)
        busy.append(sum(b - a for a, b in merged))
        for name, own in self_times(events, lo, hi).items():
            per_op[name] = per_op.get(name, 0.0) + own / len(device)
    # gaps of the first device, booked to the innermost covering span
    merged = _clip(_union([(a, b) for a, b, _ in device[0]]), lo, hi)
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    gaps = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        covering = [s for s in spans if s[0] <= mid <= s[1]]
        name = (
            min(covering, key=lambda s: s[1] - s[0])[2][len("chipbench."):]
            if covering else "unattributed"
        )
        gaps[name] = gaps.get(name, 0.0) + (b - a)
    ns = 1e-9
    return {
        "busy_s": sum(busy) / len(busy) * ns,
        "window_s": (hi - lo) * ns,
        "devices": len(device),
        "device_ops": sorted(
            ([k, v * ns] for k, v in per_op.items()), key=lambda kv: -kv[1]
        ),
        "idle_gaps": sorted(
            ([k, v * ns] for k, v in gaps.items()), key=lambda kv: -kv[1]
        ),
        "spans_s": {
            name[len("chipbench."):]: sum(
                (s[1] - s[0]) for s in spans if s[2] == name
            ) * ns
            for name in sorted({s[2] for s in spans})
        },
    }


def reduce(trace_dir: str, span_names=()):
    """The reduced trace of the newest ``.xplane.pb`` under ``trace_dir``, or
    None where no operation ran on a device plane."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, span_names)


def describe(path: str, limit: int = 6) -> str:
    """Planes, lines and the first events of a trace, for a look by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            for e in events[:limit]:
                out.append(
                    f"      {e.name!r} start={e.start_ns} dur={e.duration_ns}"
                )
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
