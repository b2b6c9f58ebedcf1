"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is first cut down to a plain record (:func:`record_from_profile`):

    {"window_ns": [lo, hi],
     "devices": [[[op name, start_ns, dur_ns], ...], ...],   # one per chip
     "host": [[span name, track, start_ns, dur_ns], ...]}

all on the profiler's clock. Every number below is a function of that
record alone, so it can be checked on a small recorded one.
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def record_from_profile(log_dir: str, anchor: str, anchor_pc_ns: int,
                        host_spans: list, window_pc_ns: tuple) -> dict:
    """Read the newest ``.xplane.pb`` under ``log_dir``.

    ``anchor`` names a ``TraceAnnotation`` entered at ``anchor_pc_ns`` on
    ``time.perf_counter_ns``; it maps the program's spans (``host_spans``:
    (name, track, t0, t1) on that clock) and the window onto the
    profiler's clock."""
    from jax.profiler import ProfileData
    paths = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise RuntimeError(f"no profile written under {log_dir}")
    data = ProfileData.from_file(str(paths[-1]))
    anchor_ns = None
    devices: dict = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events)
            elif m is None and anchor_ns is None:
                for e in line.events:
                    if e.name == anchor:
                        anchor_ns = float(e.start_ns)
                        break
    if anchor_ns is None:
        raise RuntimeError(f"annotation {anchor!r} not in the trace")
    if not devices:
        raise RuntimeError("no device ops in the trace")
    off = anchor_ns - anchor_pc_ns
    return {"window_ns": [window_pc_ns[0] + off, window_pc_ns[1] + off],
            "devices": [devices[k] for k in sorted(devices)],
            "host": [[n, tr, t0 + off, t1 - t0]
                     for n, tr, t0, t1 in host_spans]}


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _union(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(record: dict) -> list:
    """Per chip: nanoseconds of the window in which some op ran."""
    lo, hi = record["window_ns"]
    return [sum(b - a for a, b in _union((a, b) for _, a, b in
                                         _clip(ev, lo, hi)))
            for ev in record["devices"]]


def op_ns(record: dict, pattern: str) -> list:
    """Per chip: summed duration, inside the window, of ops whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    lo, hi = record["window_ns"]
    return [sum(b - a for n, a, b in _clip(ev, lo, hi) if rx.search(n))
            for ev in record["devices"]]


# "%fusion.12 = f32[8]{0} fusion(...)": instruction name, then opcode
_HLO = re.compile(r"^%?([^\s=]+?)(?:\.\d+)*(?:\.\.\S*)? = "
                  r".*?\s([a-z][\w-]*)\(")
# ops that contain other ops of the trace (a loop, a branch)
CONTAINERS = ("while", "conditional", "call")


def op_family(name: str) -> tuple:
    """(family, opcode) of a trace op named by its HLO text: the
    instruction's name without its instance number (``fusion.12`` ->
    ``fusion``); a custom call is named ``custom-call:<name>``."""
    m = _HLO.match(name)
    if m is None:
        return re.sub(r"[.:]\d+$", "", name), ""
    base, opcode = m.groups()
    return (f"custom-call:{base}" if opcode == "custom-call" else base,
            opcode)


def top_ops(record: dict, k: int = 10) -> list:
    """The op families that took most device time, summed over chips and
    averaged per chip, containers left out: [[name, seconds], ...]."""
    lo, hi = record["window_ns"]
    tot: dict = {}
    for ev in record["devices"]:
        for n, a, b in _clip(ev, lo, hi):
            f, opcode = op_family(n)
            if opcode in CONTAINERS:
                continue
            tot[f] = tot.get(f, 0.0) + (b - a)
    chips = max(len(record["devices"]), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / chips / 1e9] for n, v in best]


def idle_gaps(record: dict, track: str, k: int = 10) -> list:
    """Device idle time (averaged over chips) by what the host's ``track``
    was doing meanwhile: each idle interval is split over the innermost
    span of that track covering it, ``"(between spans)"`` elsewhere.
    [[span name, seconds], ...], largest first."""
    lo, hi = record["window_ns"]
    labels = _label_timeline(
        [(s, s + d, n) for n, tr, s, d in record["host"] if tr == track],
        lo, hi)
    tot: dict = {}
    for ev in record["devices"]:
        busy = _union((a, b) for _, a, b in _clip(ev, lo, hi))
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        j = 0
        for a, b in gaps:
            while labels[j][1] <= a:
                j += 1
            i = j
            while i < len(labels) and labels[i][0] < b:
                x, y, name = labels[i]
                tot[name] = tot.get(name, 0.0) + min(b, y) - max(a, x)
                i += 1
    chips = max(len(record["devices"]), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / chips / 1e9] for n, v in best]


def _label_timeline(spans, lo, hi) -> list:
    """[lo, hi) cut into (start, end, name) pieces, each named by the
    innermost (shortest) span covering it."""
    cuts = sorted({lo, hi} | {x for s, e, _ in spans for x in (s, e)
                              if lo < x < hi})
    out = []
    for x, y in zip(cuts, cuts[1:]):
        inner = [(e - s, n) for s, e, n in spans if s <= x and e >= y]
        out.append((x, y, min(inner)[1] if inner else "(between spans)"))
    return out
