"""From a profiler trace (`*.xplane.pb`) to numbers: the benchmark's one
reduction, so that every PR reads a trace the same way.

What it reads (looked at by hand first; `fixtures/small.xplane.pb` is a small
recorded trace the tests check this file on):
  * planes `/device:TPU:<n>`: line `XLA Modules` (one event per program run),
    `XLA Ops` (one per operation, name = the HLO line, `%short = type op(...)`)
    and `Async XLA Ops` (start-to-done spans of asynchronous operations);
  * plane `/host:CPU`: the host threads; the benchmark's own
    `jax.profiler.TraceAnnotation`s are the events named `bench:...`.
Device and host lines are on clocks that differ by about a millisecond, so
everything that is a share of time is taken on the device's clock alone, and
host spans only name what the host was doing in a gap.

The window is whole periods of the round program: from the start of its
second traced run to the start of its last, so it holds `rounds` runs with the
gaps that follow them, and nothing of the profiler's own start and stop.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
BENCH_SPAN = "bench:"
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Ops:
    """One device line: start and duration in seconds, and the HLO short name
    (`%fusion.12`) and kind (`fusion`, `custom-call`, ...) of each event."""
    start: np.ndarray
    dur: np.ndarray
    short: list
    kind: list

    @property
    def end(self):
        return self.start + self.dur


def _parse(name: str) -> tuple:
    short, _, rest = name.partition(" = ")
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest) if rest else None
    return short.strip(), (m.group(1) if m else short.strip().lstrip("%"))


def _line(events) -> Ops:
    start, dur, short, kind = [], [], [], []
    for e in events:
        start.append(e.start_ns)
        dur.append(e.duration_ns)
        s, k = _parse(e.name)
        short.append(s)
        kind.append(k)
    return Ops(np.asarray(start, np.float64) * 1e-9,
               np.asarray(dur, np.float64) * 1e-9, short, kind)


def read(path: str) -> dict:
    """{"devices": {ordinal: {"modules", "ops", "async"}}, "spans": [(name,
    start_s, end_s)]} from one xplane file."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "spans": []}
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            out["devices"][int(m.group(1))] = {
                key: _line(lines[name].events) if name in lines
                else Ops(np.zeros(0), np.zeros(0), [], [])
                for key, name in (("modules", "XLA Modules"),
                                  ("ops", "XLA Ops"),
                                  ("async", "Async XLA Ops"))}
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(BENCH_SPAN):
                        out["spans"].append(
                            (e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    return out


def union(start: np.ndarray, end: np.ndarray, lo: float, hi: float):
    """The intervals clipped to [lo, hi] and merged: (starts, ends)."""
    start, end = np.clip(start, lo, hi), np.clip(end, lo, hi)
    keep = end > start
    start, end = start[keep], end[keep]
    if not len(start):
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    first = np.concatenate([[True], start[1:] > reach[:-1]])
    return start[first], np.concatenate(
        [reach[:-1][first[1:]], reach[-1:]])


def covered(start, end, lo, hi) -> float:
    s, e = union(start, end, lo, hi)
    return float(np.sum(e - s))


def round_module(modules: Ops) -> str:
    """The program that takes most of the device's time: the round."""
    total = {}
    for name, d in zip(modules.short, modules.dur):
        total[name] = total.get(name, 0.0) + d
    return max(total, key=total.get)


def _is_collective(short: str) -> bool:
    return "all-reduce" in short or "all-gather" in short or \
        "reduce-scatter" in short or "collective-permute" in short


def reduce(trace: dict) -> dict:
    """The numbers the readers take. Raises ValueError when the trace holds
    fewer than two runs of the round program on some device (no whole
    period)."""
    per_device, names = [], {}
    for ordinal, dev in sorted(trace["devices"].items()):
        mods, ops, asy = dev["modules"], dev["ops"], dev["async"]
        if not len(mods.start):
            continue
        rname = round_module(mods)
        starts = np.sort(mods.start[[n == rname for n in mods.short]])
        if len(starts) > 2:
            # the first traced period holds the stall of starting the
            # profiler (the host was busy with it, the queue ran dry)
            starts = starts[1:]
        if len(starts) < 2:
            raise ValueError(f"device {ordinal}: {len(starts)} run(s) of "
                             f"{rname} in the trace, need two or more")
        lo, hi = float(starts[0]), float(starts[-1])
        busy_s, busy_e = union(ops.start, ops.end, lo, hi)
        inside = (ops.start >= lo) & (ops.start < hi)
        kernel = inside & np.asarray([k == "custom-call" for k in ops.kind])
        # per-name time: a `while` or `call` spans the operations of its
        # body, which are listed too, so the containers are left out
        for name, kind, d in zip(np.asarray(ops.short, object)[inside],
                                 np.asarray(ops.kind, object)[inside],
                                 ops.dur[inside]):
            if kind not in _CONTAINERS:
                names[name] = names.get(name, 0.0) + float(d)
        # collectives: start-to-done spans, and the part of them during
        # which no other operation ran on this device
        coll = [_is_collective(s) for s in asy.short]
        sync = [_is_collective(s) for s in ops.short]
        c_start = np.concatenate([asy.start[coll], ops.start[sync]])
        c_end = np.concatenate([asy.end[coll], ops.end[sync]])
        cs, ce = union(c_start, c_end, lo, hi)
        compute = ~np.asarray(sync, bool) if len(sync) else np.zeros(0, bool)
        exposed = sum(
            (b - a) - covered(ops.start[compute], ops.end[compute], a, b)
            for a, b in zip(cs, ce))
        gaps_lo = np.concatenate([[lo], busy_e])
        gaps_hi = np.concatenate([busy_s, [hi]])
        per_device.append({
            "ordinal": ordinal, "round_module": rname,
            "rounds": len(starts) - 1, "window_s": hi - lo,
            "busy_s": float(np.sum(busy_e - busy_s)),
            "kernel_s": float(np.sum(ops.dur[kernel])),
            "kernel_calls": int(np.sum(kernel)),
            "collective_s": float(np.sum(ce - cs)),
            "collective_exposed_s": float(exposed),
            "gaps": [(float(a), float(b)) for a, b in zip(gaps_lo, gaps_hi)
                     if b > a]})
    if not per_device:
        raise ValueError("no device plane with a program run in the trace")
    n = len(per_device)
    fullest = max(per_device, key=lambda d: d["busy_s"])
    return {
        "devices": per_device,
        "rounds": min(d["rounds"] for d in per_device),
        "window_s": sum(d["window_s"] for d in per_device) / n,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "fullest": fullest,
        "device_ops": sorted(((k, v / n) for k, v in names.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": _name_gaps(fullest["gaps"], trace["spans"]),
    }


def _name_gaps(gaps: list, spans: list) -> list:
    """Idle time of the fullest device by what the host was doing: each gap
    goes to the innermost benchmark span that holds its middle."""
    total = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        holding = [(e - s, name) for name, s, e in spans if s <= mid <= e]
        name = min(holding)[1] if holding else "(no benchmark span)"
        total[name] = total.get(name, 0.0) + (b - a)
    return sorted(total.items(), key=lambda kv: -kv[1])
