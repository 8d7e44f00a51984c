"""The program's own host spans in a JAX profiler trace, per name, and the
device's idle time laid against them.

The program names each host phase of its round loop with
``jax.profiler.TraceAnnotation`` (``src/repro/fl/spans.py``); every such
span carries a ``round`` stat, which is how this module tells them from
the runtime's own host events. ``load`` reads the device planes as
``trace.load`` does and keeps each host line (a thread) apart, in one pass
over the file; ``summarize`` reduces them:

* each span is clipped to the traced window, the span of the device's
  work (``trace.device_span``);
* per span name: calls, total seconds, self seconds (the span less the
  program spans nested in it on its line), the idle seconds of device 0
  it holds as the innermost span, and the sums of its counts;
* the main line is the one holding the ``round`` spans, the thread that
  launches the device's programs; device 0's idle time goes to the
  innermost span below ``round`` on that line, never to the worker's
  spans, and what no such span covers stays unattributed.

A trace with no program spans (a program without them) gives an empty
``spans`` and no main line; the readers then report nothing.
"""
from __future__ import annotations

import functools
import re
from pathlib import Path

from chipbench import trace as TR

ROUND = "round"
TRACE_DIR = (".chipbench", "trace")


def load(path: Path) -> tuple:
    """(devices, lines): the device planes in ``trace.load``'s form
    (``{"devices": {name: {"modules", "ops"}}}``) and each host line's
    program spans (events with a ``round`` stat), one list per line, in
    the trace's order."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, lines = {}, []
    for plane in pd.planes:
        is_dev = re.fullmatch(r"/device:TPU:\d+", plane.name) is not None
        if not is_dev and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if is_dev:
                d = devices.setdefault(plane.name, {"modules": [], "ops": []})
                kind = {"XLA Modules": "modules",
                        "XLA Ops": "ops"}.get(line.name)
                if kind is None:
                    continue
                d[kind].extend(TR.Event(e.name, e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9, dict(e.stats))
                               for e in line.events)
                continue
            evs = []
            for e in line.events:
                stats = dict(e.stats)
                if ROUND in stats:
                    evs.append(TR.Event(e.name, e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9, stats))
            if evs:
                lines.append(evs)
    return {"devices": devices}, lines


def _measure(ivs) -> float:
    return sum(b - a for a, b in ivs)


def _minus(lo: float, hi: float, holes: list) -> list:
    """[lo, hi] less the merged intervals ``holes``."""
    out, at = [], lo
    for a, b in TR.union(holes, lo, hi):
        if a > at:
            out.append([at, a])
        at = max(at, b)
    if hi > at:
        out.append([at, hi])
    return out


def _overlap(xs: list, ys: list) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(raw: dict, lo: float, hi: float) -> list:
    """Device 0's idle intervals inside [lo, hi]: the window less the
    union of its operations. ``trace.reduce`` finds the same gaps but
    keeps only their lengths and labels, not where they lie."""
    d0 = raw["devices"][sorted(raw["devices"])[0]]
    evs = d0["ops"] or d0["modules"]
    return _minus(lo, hi, [(e.start, e.end) for e in evs])


def summarize(raw: dict, lines: list) -> dict:
    """The spans of ``lines`` per name inside the device window of
    ``raw`` (``trace.load``'s device planes), and device 0's idle time."""
    lo, hi = TR.device_span(raw)
    idle = idle_intervals(raw, lo, hi)
    main = max(lines, key=lambda ln: sum(e.name == ROUND for e in ln),
               default=[])
    if not any(e.name == ROUND for e in main):
        main = []
    spans = {}
    attributed = 0.0
    for line in lines:
        for s in line:
            a, b = max(s.start, lo), min(s.end, hi)
            if b <= a:
                continue
            kids = [(k.start, k.end) for k in line if k is not s
                    and s.start <= k.start and k.end <= s.end
                    and (k.start, -k.dur) > (s.start, -s.dur)]
            own = _minus(a, b, kids)
            rec = spans.setdefault(s.name, {
                "calls": 0, "seconds": 0.0, "self_s": 0.0, "idle_s": 0.0,
                "counts": {}})
            rec["calls"] += 1
            rec["seconds"] += b - a
            rec["self_s"] += _measure(own)
            for k, v in s.stats.items():
                if k != ROUND and isinstance(v, (int, float)):
                    rec["counts"][k] = rec["counts"].get(k, 0) + v
            if line is main and s.name != ROUND:
                held = _overlap(own, idle)
                rec["idle_s"] += held
                attributed += held
    idle_s = _measure(idle)
    return {"spans": spans, "window_s": hi - lo, "idle_s": idle_s,
            "idle_attributed_s": attributed, "main": bool(main)}


@functools.lru_cache(maxsize=4)
def _read(path: str, _mtime_ns: int, _size: int) -> dict:
    return summarize(*load(Path(path)))


def read(trace_dir: Path) -> dict:
    """``summarize`` of the trace under ``trace_dir``, read once per file."""
    path = TR.find_xplane(trace_dir)
    st = path.stat()
    return _read(str(path), st.st_mtime_ns, st.st_size)


def of(ctx) -> dict:
    """The spans of the run a metric reader's ``ctx`` describes: the trace
    ``run.py`` records under ``<checkout>/.chipbench/trace``."""
    return read(Path(ctx["cell"].root).joinpath(*TRACE_DIR))


def per_round_ms(ctx, names, field: str = "seconds"):
    """Milliseconds a round of ``field`` summed over ``names``, over the
    traced rounds (``ctx["rounds"]``, as the device readers divide);
    None where none of them ran."""
    got = [of(ctx)["spans"].get(n) for n in names]
    got = [g for g in got if g is not None]
    if not got:
        return None
    return 1e3 * sum(g[field] for g in got) / ctx["rounds"]
