"""Device time by program region, and the program's spans as the profiler
stamped them.

The program wraps its fused train step in ``jax.named_scope`` regions
(``src/repro/obs/scopes.py``); each HLO instruction carries the scope
path in its ``op_name`` metadata, e.g.
``jit(step)/while/body/closed_call/transpose(jvp(layers))/dot_general``.
The names are spelled here as literals, not imported from the program: a
rename then shows as a missing reading instead of being followed.

:func:`from_profile` reads two additions to the record of
:func:`bench.tracereduce.record_from_profile` from the same profile:

    record["device_scopes"]   per chip, aligned with record["devices"]:
                              each op's scope path ("gather/kernel",
                              "layers", ...) or None
    record["host_native"]     [[span name, thread, start_ns, dur_ns], ...]
                              the program's spans on the profiler's clock

Every number below is a function of such a record alone, so it can be
checked on a small recorded one.
"""
from __future__ import annotations

import bisect
import json
import os
import re
import tempfile
from pathlib import Path

from bench import tracereduce

SCOPES = ("exchange", "gather", "kernel", "layers", "update")

_WRAPPED = re.compile(r"[\w.-]+\((.*)\)")
_INSTR = re.compile(r"^%?(\S+?) = ")
_PROGRAM = re.compile(r"\((\d+)\)$")      # "jit_step(1547...)"


def _unwrap(component: str) -> str:
    """``transpose(jvp(layers))`` -> ``layers``."""
    m = _WRAPPED.fullmatch(component)
    while m is not None:
        component = m.group(1)
        m = _WRAPPED.fullmatch(component)
    return component


def scope_path(op_name: str | None) -> str | None:
    """The known scopes on an op's name-stack path, outermost first
    (``"gather/kernel"``), or None. The last component is the primitive
    (``.../exchange/gather`` is a gather primitive in ``exchange``), and
    is not a scope."""
    if not op_name:
        return None
    known: list = []
    for c in op_name.split("/")[:-1]:
        c = _unwrap(c)
        if c in SCOPES and (not known or known[-1] != c):
            known.append(c)
    return "/".join(known) or None


def _op_names(path: str) -> dict:
    """(program id, HLO instruction name) -> ``op_name`` of every op the
    profile ran, from the HLO it stores (xprof's ``hlo_stats`` tool; its
    framework op name is ``op_name:op_type``). xprof caches what it
    derives beside the profile it reads, so it reads a link to the
    profile in a directory of its own."""
    from xprof.convert import raw_to_tool_data
    with tempfile.TemporaryDirectory() as tmp:
        link = os.path.join(tmp, os.path.basename(path))
        os.symlink(os.path.abspath(path), link)
        raw, _ = raw_to_tool_data.xspace_to_tool_data([link], "hlo_stats",
                                                      {})
    table = json.loads(raw)
    cols = [c["id"] for c in table["cols"]]
    out = {}
    for row in table["rows"]:
        r = dict(zip(cols, (c.get("v") for c in row["c"])))
        out[(str(r["program_id"]), r["hlo_op_name"])] = \
            str(r["tf_op_name"] or "").rsplit(":", 1)[0]
    return out


def from_profile(log_dir: str, span_names) -> dict:
    """``device_scopes`` and ``host_native`` (module doc) of the newest
    profile under ``log_dir``, aligned with what
    :func:`bench.tracereduce.record_from_profile` reads from it. The op
    names of a TPU trace are HLO text without metadata: each op's
    ``op_name`` comes from the HLO the profile stores, keyed by the
    program of the ``XLA Modules`` event it runs in and its instruction
    name."""
    from jax.profiler import ProfileData
    path = str(sorted(Path(log_dir).rglob("*.xplane.pb"),
                      key=lambda p: p.stat().st_mtime)[-1])
    data = ProfileData.from_file(path)
    names = _op_names(path)
    devices: dict = {}
    for plane in data.planes:
        m = tracereduce.DEVICE_PLANE.match(plane.name)
        if m is None:
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = [(e.start_ns, e.start_ns + e.duration_ns,
                 _PROGRAM.search(e.name)) for e in lines.get("XLA Modules",
                                                             [])]
        starts = [a for a, _, _ in mods]
        paths = devices.setdefault(int(m.group(1)), [])
        for e in lines.get(tracereduce.OPS_LINE, []):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            prog = (mods[i][2].group(1) if i >= 0 and mods[i][2]
                    and e.start_ns <= mods[i][1] else None)
            ins = _INSTR.match(e.name)
            paths.append(scope_path(names.get((prog, ins and ins.group(1)))))
    return {"device_scopes": [devices[k] for k in sorted(devices)],
            "host_native": host_native(data, span_names)}


def device_scopes(record: dict) -> list:
    """Per chip, each op's scope path: ``record["device_scopes"]``, or no
    scope for any op where the record has none."""
    got = record.get("device_scopes")
    return got if got is not None else [[None] * len(ev)
                                        for ev in record["devices"]]


def _in(path, scope: str) -> bool:
    return path is not None and (path == scope
                                 or path.startswith(scope + "/"))


def scope_ns(record: dict, scope: str, exclude: str | None = None) -> list:
    """Per chip: device time inside the window of the ops in ``scope``
    (a scope path and everything nested in it), without those in
    ``exclude``. Containers (``while``, ``conditional``, ``call``) are left
    out, as in :func:`bench.tracereduce.top_ops`: their bodies are counted
    op by op. A fusion carries its root instruction's metadata."""
    lo, hi = record["window_ns"]
    out = []
    for ev, paths in zip(record["devices"], device_scopes(record)):
        tot = 0.0
        for (name, s, d), path in zip(ev, paths):
            if not _in(path, scope) or (exclude and _in(path, exclude)):
                continue
            a, b = max(s, lo), min(s + d, hi)
            if b > a and tracereduce.op_family(name)[1] \
                    not in tracereduce.CONTAINERS:
                tot += b - a
        out.append(tot)
    return out


def unscoped_ns(record: dict) -> tuple:
    """(ns in ops of no scope, ns in all ops), inside the window, summed
    over chips, containers left out."""
    lo, hi = record["window_ns"]
    none = every = 0.0
    for ev, paths in zip(record["devices"], device_scopes(record)):
        for (name, s, d), path in zip(ev, paths):
            a, b = max(s, lo), min(s + d, hi)
            if b <= a or tracereduce.op_family(name)[1] \
                    in tracereduce.CONTAINERS:
                continue
            every += b - a
            if path is None:
                none += b - a
    return none, every


def host_native(data, names) -> list:
    """The program's spans as the profiler stamped them: every event named
    in ``names`` on a host plane of a ``jax.profiler.ProfileData``, as
    [name, thread, start_ns, dur_ns]; ``thread`` is the line's name and
    its index in the plane (several threads can share a name)."""
    names = set(names)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for j, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in names:
                    out.append([e.name, f"{line.name}#{j}",
                                float(e.start_ns), float(e.duration_ns)])
    out.sort(key=lambda s: s[2])
    return out


def span_ns_per_build(record: dict, name: str):
    """Nanoseconds of the program's spans named ``name`` per ``plan.build``
    span in ``record["host"]``; None without a build or without such a
    span."""
    spans = record["host"]
    builds = sum(1 for s in spans if s[0] == "plan.build")
    mine = [s[3] for s in spans if s[0] == name]
    if not builds or not mine:
        return None
    return sum(mine) / builds
