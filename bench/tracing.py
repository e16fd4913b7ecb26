"""Profiler trace -> device busy time, kernel time, top ops and idle gaps.

A trace is first cut down to a compact form, kept in memory and written
beside the run as JSON: the device's op events ``[name, start_ns,
duration_ns, device]`` (the ``XLA Ops`` line of each device plane; a loop
op's line holds its body's ops nested inside it) and the host's events
``[name, start_ns, duration_ns, thread]``, both on the profiler's one
clock.  Every reduction below reads only that form, so the test beside
this file checks it on a small recorded trace.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
WINDOW = "bench.window"          # host annotation around the measured window
HOST_PREFIX = "bench."           # the benchmark's own host annotations


def compact(profile_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, modules, host, devices = [], [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = [ln for ln in plane.lines if ln.name == DEVICE_LINE]
            for ln in lines:
                device += [[e.name, int(e.start_ns), int(e.duration_ns),
                            devices] for e in ln.events]
            for ln in plane.lines:
                if ln.name == MODULE_LINE and lines:
                    modules += [[e.name.split("(")[0], int(e.start_ns),
                                 int(e.duration_ns), devices]
                                for e in ln.events]
            devices += bool(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns),
                          ln.name] for e in ln.events]
    for evs in (device, modules, host):
        evs.sort(key=lambda e: e[1])
    return {"devices": devices, "device": device, "modules": modules,
            "host": host}


def save(tr: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window(tr: dict) -> tuple[int, int]:
    """(start, end) ns of the measured window's host annotation."""
    w = [e for e in tr["host"] if e[0] == WINDOW]
    if not w:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    return w[0][1], w[0][1] + w[0][2]


def _clip(events, t0: int, t1: int):
    for e in events:
        a, b = max(e[1], t0), min(e[1] + e[2], t1)
        if b > a:
            yield e[0], a, b


def busy_intervals(tr: dict, t0: int, t1: int,
                   device: int = 0) -> list[tuple[int, int]]:
    """Union of one device's op intervals inside [t0, t1), merged, sorted."""
    out: list[list[int]] = []
    evs = [e for e in tr["device"] if e[3] == device]
    for _, a, b in sorted(_clip(evs, t0, t1), key=lambda x: x[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: dict, t0: int, t1: int) -> float:
    """Seconds in which an op ran on a device, averaged over the devices."""
    n = max(tr["devices"], 1)
    return sum(b - a for dev in range(n)
               for a, b in busy_intervals(tr, t0, t1, dev)) / 1e9 / n


def _leaves(events):
    """The ops that hold no other op (a loop op holds its body's ops)."""
    by_dev: dict[int, list] = {}
    for e in events:
        by_dev.setdefault(e[3], []).append(e)
    for evs in by_dev.values():
        evs.sort(key=lambda e: e[1])
        for e, nxt in zip(evs, evs[1:] + [None]):
            if nxt is None or nxt[1] >= e[1] + e[2]:
                yield e


def kernel_s(tr: dict, patterns: list[str], t0: int, t1: int) -> float:
    """Device seconds of the ops whose name matches any of ``patterns``,
    summed over devices."""
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    return sum(b - a for name, a, b in _clip(_leaves(tr["device"]), t0, t1)
               if rx.search(name)) / 1e9


def _module_of(tr: dict, ops: list) -> list[str]:
    """The program (``XLA Modules`` event) each op, sorted by start, ran in."""
    mods = tr.get("modules", [])
    out, i = [], 0
    for op in ops:
        while i < len(mods) and mods[i][1] + mods[i][2] <= op[1]:
            i += 1
        hit = i < len(mods) and mods[i][1] <= op[1]
        out.append(mods[i][0] if hit else "")
    return out


def top_ops(tr: dict, t0: int, t1: int, k: int = 10) -> list[list]:
    """The ``k`` ops with the most device time, counting ops that hold no
    other op, each named ``program:instruction``, [[name, seconds]]."""
    tot: dict[str, int] = {}
    ops = sorted(_clip(_leaves(tr["device"]), t0, t1), key=lambda e: e[1])
    for (name, a, b), mod in zip(ops, _module_of(tr, ops)):
        name = f"{mod}:{name.split(' = ')[0].strip()}"
        tot[name] = tot.get(name, 0) + (b - a)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def _labels_at(tr: dict, times: list[int]) -> list[str]:
    """What the host was doing at each of ``times`` (ascending): the
    innermost benchmark annotation covering it and, inside that, the
    innermost other event on the benchmark's own thread."""
    import heapq

    threads = {e[3] for e in tr["host"] if e[0].startswith(HOST_PREFIX)}
    host = [e for e in tr["host"] if e[3] in threads and e[0] != WINDOW]
    heaps: tuple[list, list] = ([], [])      # (duration, end, name)
    out, i = [], 0
    for t in times:
        while i < len(host) and host[i][1] <= t:
            name, a, dur, _ = host[i]
            i += 1
            heapq.heappush(heaps[not name.startswith(HOST_PREFIX)],
                           (dur, a + dur, name))
        top = []
        for h in heaps:
            while h and h[0][1] <= t:        # ended before t: gone for good
                heapq.heappop(h)
            top.append(h[0][2] if h else None)
        label = top[0] or "outside any call"
        out.append(f"{label} / {top[1]}" if top[1] else label)
    return out


def idle_gaps(tr: dict, t0: int, t1: int, k: int = 10) -> list[list]:
    """Device idle time inside [t0, t1) by what the host was doing at each
    gap's midpoint: the ``k`` largest totals, [[label, seconds]]."""
    busy = busy_intervals(tr, t0, t1)         # the first device's gaps
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    labels = _labels_at(tr, [(a + b) // 2 for a, b in gaps])
    tot: dict[str, int] = {}
    for (a, b), label in zip(gaps, labels):
        tot[label] = tot.get(label, 0) + (b - a)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[label, ns / 1e9] for label, ns in top]
