"""Reduce a profiler trace to device busy time, per-op device time and
idle gaps labelled by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain list of events (device ops and modules of every TPU, and the
harness's ``bench.*`` host spans); ``reduce`` works on that list alone,
so a recorded list checks it without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.trace_window"
HOST_PREFIX = "bench."
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
NAME_CHARS = 400      # an op's HLO text up to its operands' shapes
CONTAINERS = {"while", "conditional", "call"}   # ops that hold other ops

# an event: (kind, chip, name, start_ns, dur_ns) with kind "op", "module"
# or "host"
Event = Tuple[str, int, str, float, float]


def stable_name(name: str) -> str:
    """An op's or module's name without the numbering XLA and the
    profiler add: ``jit_step(12)``, ``fusion.12`` and the HLO text
    ``%fusion.12 = bf16[8]{0} fusion(...)`` become ``jit_step``,
    ``fusion`` and ``fusion``."""
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
    prev = None
    while prev != name:
        prev = name
        name = re.sub(r"(\(\d+\)|\.\d+)$", "", name)
    return name


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    events: List[Event] = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                kind = {OP_LINE: "op", MODULE_LINE: "module"}.get(line.name)
                if kind is None:
                    continue
                for e in line.events:
                    events.append((kind, chip, e.name[:NAME_CHARS], e.start_ns,
                                   e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        events.append(("host", -1, e.name, e.start_ns,
                                       e.duration_ns))
    return events


def save_events(events: List[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path: str) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f)]


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def window(events: List[Event]) -> Optional[Tuple[float, float]]:
    """The traced window, from the harness's window span."""
    spans = [(e[3], e[3] + e[4]) for e in events
             if e[0] == "host" and e[2] == WINDOW_SPAN]
    return max(spans, key=lambda s: s[1] - s[0]) if spans else None


def reduce(events: List[Event], top: int = 10) -> Dict:
    """busy_s (mean over the chips seen), window_s, device time and call
    count per op (leaf ops: not ``while`` and the like) and per module by
    stable name, and idle gaps by the host span that covers most of each;
    ``ops`` and ``modules`` keep the window's device events."""
    win = window(events)
    if win is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = win
    ops = [e for e in events if e[0] == "op" and e[3] < hi and e[3] + e[4] > lo]
    mods = [e for e in events if e[0] == "module" and e[3] < hi and e[3] + e[4] > lo]
    chips = sorted({e[1] for e in ops + mods})
    busy_by_chip = {}
    merged_by_chip = {}
    busy_src = ops or mods        # a device without an op line: modules
    for c in chips:
        iv = _merge(_clip([(e[3], e[3] + e[4]) for e in busy_src if e[1] == c],
                          lo, hi))
        merged_by_chip[c] = iv
        busy_by_chip[c] = sum(e - s for s, e in iv)
    busy_ns = sum(busy_by_chip.values()) / len(chips) if chips else 0.0

    def totals(evs):
        t, n = defaultdict(float), defaultdict(int)
        for e in evs:
            k = stable_name(e[2])
            t[k] += e[4] / 1e9
            n[k] += 1
        return dict(t), dict(n)

    op_s, op_n = totals([e for e in ops if stable_name(e[2]) not in CONTAINERS])
    mod_s, mod_n = totals(mods)

    # idle gaps on the first chip, each labelled by the host span that
    # overlaps it most (the harness's spans do not nest)
    host = sorted((e[3], e[3] + e[4], e[2]) for e in events
                  if e[0] == "host" and e[2] != WINDOW_SPAN)
    ends = [h[1] for h in host]
    gaps = []
    iv = merged_by_chip.get(chips[0], []) if chips else []
    edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        best, label = 0.0, "none"
        i = bisect_right(ends, s)
        while i < len(host) and host[i][0] < e:
            ov = min(host[i][1], e) - max(host[i][0], s)
            if ov > best:
                best, label = ov, host[i][2]
            i += 1
        gaps.append((label, (e - s) / 1e9))
    idle_by_label = defaultdict(float)
    for label, sec in gaps:
        idle_by_label[label] += sec

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "chips": len(chips), "op_s": op_s, "op_n": op_n,
            "module_s": mod_s, "module_n": mod_n,
            "device_ops": ranked(op_s), "idle_gaps": ranked(idle_by_label),
            "ops": ops, "modules": mods}
