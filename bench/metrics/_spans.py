"""Shared by the program-span readers: the program tracer's stages over
the window, and its retained spans on the profiler's clock.

With the program's span tracer on, ``Metrics.snapshot()`` carries
``"stages"``, each stage's count and total so far. The harness takes
that snapshot when the window opens and when it closes
(``loop.stats_open``, ``loop.stats_close``), so their difference is
each stage's exact count and total over the window. A program without
the tracer has no ``"stages"``, and its readers return None. Like the
device readers, they also read nothing from a trace that saw no chip:
the benchmark runs on the chip, and a traced run off it (the CPU tests)
prints only the host-clock metrics.

In a traced run, the profiler's host events are read back from the
run's trace, the ``bench.trace_window`` annotation's start and end are
paired with ``loop.trace_s`` (``perf_counter`` read at once inside it),
and the tracer's retained spans are mapped onto the profiler's clock
through those two anchors (``repro.obs.map_clock``). The result is
cached on ``rec`` and written, with the window's stage tree and the
clock check, to ``bench/out/spans/<cell>.json``.

Nothing here imports the program at load time: the readers load
beside a program that may lack the tracer's newer stages.
"""
from __future__ import annotations

import bisect
import json
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce
from bench.trace_reduce import _clip as clip, _merge as merge

OUT = Path(__file__).resolve().parents[1] / "out"
TRACE_ROOT = OUT / "trace"            # where bench.run traces a cell
SPANS_ROOT = OUT / "spans"
APPEND_SPAN = "bench.append_kv"
READBACK_SPAN = "bench.readback"

Interval = Tuple[float, float]


# ------------------------------------------------------------ window deltas
def window_stages(rec) -> Optional[Dict[str, Dict[str, int]]]:
    """Each stage's ``{count, total_ns}`` over the window, or None when
    the program keeps no stage aggregates (its tracer is off) or the
    trace saw no chip."""
    if not rec.trace["chips"]:
        return None
    a = rec.loop.stats_open.get("stages")
    b = rec.loop.stats_close.get("stages")
    if a is None or b is None:
        return None
    zero = {"count": 0, "total_ns": 0}
    return {name: {k: t[k] - a.get(name, zero)[k] for k in zero}
            for name, t in b.items()}


def total_ms(stages: Dict, name: str) -> float:
    return stages.get(name, {}).get("total_ns", 0) / 1e6


def count(stages: Dict, name: str) -> int:
    return stages.get(name, {}).get("count", 0)


def ms_per(stages: Dict, name: str, n: int) -> float:
    """``name``'s window total in ms over ``n``; 0.0 when ``n`` is 0."""
    return total_ms(stages, name) / n if n else 0.0


def stage_tree(stages: Dict) -> Dict[str, Dict]:
    """The window's stage tree: count, total and self time (total less
    the declared children's totals) of every stage that saw a span."""
    from repro.obs.tracer import STAGES

    parent = dict(STAGES)
    tree = {}
    for name, t in stages.items():
        if not t["count"]:
            continue
        kids = sum(stages.get(c, {}).get("total_ns", 0)
                   for c, p in STAGES if p == name)
        tree[name] = {"count": t["count"], "total_ms": t["total_ns"] / 1e6,
                      "self_ms": max(0, t["total_ns"] - kids) / 1e6,
                      "parent": parent.get(name)}
    return tree


# ------------------------------------------------------------- intervals
def complement(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that ``merged`` (sorted, disjoint)
    leaves free."""
    edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def overlap(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(iv: List[Interval]) -> float:
    return sum(e - s for s, e in iv)


# ------------------------------------------------------ the profiler's clock
def host_events(rec) -> List[trace_reduce.Event]:
    """The traced run's ``bench.*`` host events."""
    return trace_reduce.load(trace_reduce.find_xplane(
        str(TRACE_ROOT / rec.cell.name)))


def chip_idle(trace: Dict, lo: float, hi: float) -> List[Interval]:
    """The first chip's idle intervals in ``[lo, hi]``."""
    events = trace["ops"] or trace["modules"]
    chip = min(e[1] for e in events)
    busy = merge(clip([(e[3], e[3] + e[4]) for e in events if e[1] == chip],
                      lo, hi))
    return complement(busy, lo, hi)


def clock_check(spans: List[Interval], annotations: List[Interval]) -> Dict:
    """How far each span reaches outside the annotation that overlaps it
    most, in us, and how many spans overlap none."""
    annotations = sorted(annotations)
    ends = [e for _, e in annotations]
    worst, unmatched = 0.0, 0
    for s, e in spans:
        best, host = 0.0, None
        i = bisect.bisect_right(ends, s)
        while i < len(annotations) and annotations[i][0] < e:
            ov = min(e, annotations[i][1]) - max(s, annotations[i][0])
            if ov > best:
                best, host = ov, annotations[i]
            i += 1
        if host is None:
            unmatched += 1
            continue
        worst = max(worst, host[0] - s, e - host[1])
    return {"spans": len(spans), "unmatched": unmatched,
            "max_overhang_us": max(0.0, worst) / 1e3}


def traced(rec) -> Optional[Dict]:
    """The tracer's spans over the traced window, on the profiler's
    clock (cached on ``rec``): the union of ``sched_task`` spans
    (``background``), the first chip's idle intervals (``idle``), the
    clock check of ``kv_append`` spans against the harness's
    ``bench.append_kv`` annotations, and whether the retained store
    still holds every span of the window. None when the tracer is off
    or the trace saw no chip."""
    if getattr(rec, "program_spans", None) is not None:
        return rec.program_spans
    tracer = getattr(rec.loop.system.metrics, "tracer", None)
    if tracer is None or not rec.trace["chips"]:
        return None
    from repro.obs import STAGE_NAMES, map_clock

    events = host_events(rec)
    lo, hi = trace_reduce.window(events)
    a, b = rec.trace_span
    anchors = ((a * 1e9, lo), (b * 1e9, hi))
    stage, t0, dur, _, _ = tracer.span_arrays()
    start = map_clock(t0, *anchors)
    end = map_clock(t0 + dur, *anchors)

    def in_window(name: str) -> List[Interval]:
        sel = stage == STAGE_NAMES.index(name)
        return [(s, e) for s, e in zip(start[sel].tolist(), end[sel].tolist())
                if e > lo and s < hi]

    host = [(e[3], e[3] + e[4], e[2]) for e in events if e[0] == "host"]
    background = merge(clip(in_window("sched_task"), lo, hi))
    appends = [(s, e) for s, e in in_window("kv_append") if s >= lo and e <= hi]
    idle = chip_idle(rec.trace, lo, hi)
    readback = merge([(s, e) for s, e, n in host if n == READBACK_SPAN])
    idle_rb = overlap(idle, readback)
    out = {
        "window_ns": (lo, hi),
        "background": background,
        "idle": idle,
        "clock_check": clock_check(
            appends, [(s, e) for s, e, n in host if n == APPEND_SPAN]),
        # the store drops the oldest spans first: none of the window's is
        # lost while a retained span ended before the window opened
        "retained": {"spans": int(len(stage)),
                     "dropped_spans": int(tracer.dropped_spans),
                     "window_complete": bool(tracer.dropped_spans == 0 or (
                         len(end) and end.min() < lo))},
        "idle_s": {
            "all": length(idle) / 1e9,
            "background": length(overlap(idle, background)) / 1e9,
            "readback": length(idle_rb) / 1e9,
            "readback_background": length(overlap(idle_rb, background)) / 1e9},
    }
    rec.program_spans = out
    _write(rec, out)
    return out


def _write(rec, spans: Dict) -> None:
    """Keep the traced window's findings beside the trace, for reading
    after the run."""
    stages = window_stages(rec)
    doc = {k: v for k, v in spans.items()
           if k not in ("background", "idle", "window_ns")}
    doc["window_s"] = (spans["window_ns"][1] - spans["window_ns"][0]) / 1e9
    doc["stage_tree"] = stage_tree(stages) if stages is not None else None
    SPANS_ROOT.mkdir(parents=True, exist_ok=True)
    (SPANS_ROOT / f"{rec.cell.name}.json").write_text(json.dumps(doc, indent=1))
