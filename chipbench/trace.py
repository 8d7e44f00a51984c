"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
event lists: per TPU device, its program executions ("XLA Modules") and
its operations ("XLA Ops"); and every host thread's events. ``reduce``
turns those lists into the numbers the per-layer readers use, so a test
can feed it events of its own:

* the window: from the start of the first program that ran on a device
  to the end of the last, so that neither the profiler's start nor the
  writing of the trace at its stop counts as time the device sat idle;
* busy time: the union of the device's operation intervals inside the
  window, averaged over the devices; idle share is 1 - busy / window;
* per program (a jitted function, by its name) and per Pallas kernel (by
  its ``name``): calls and device seconds;
* each kernel call's bytes, summed over the shapes of its operands and
  results as the HLO instruction states them;
* the idle gaps of device 0, each labelled by what the host was doing in
  it: the dispatch of a named jit, a transfer, or Python when no runtime
  event covers the gap.

Times are in seconds, on the trace's own clock.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

KERNELS = ("magnitude_histogram", "hybrid_compress", "recover")
DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "s4": 0.5, "u4": 0.5}
_SHAPE = re.compile(r"\b(f64|s64|u64|f32|s32|u32|bf16|f16|s16|u16|s8|u8|"
                    r"pred|s4|u4)\[([0-9,]*)\]")
MIN_GAP_S = 1e-6


@dataclasses.dataclass
class Event:
    name: str
    start: float
    dur: float
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


def program_name(module: str) -> str:
    """``jit_tier_chunk_defer(42)`` -> ``tier_chunk_defer``."""
    name = re.sub(r"\(-?\d+\)$", "", module)
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def hlo_bytes(text: str) -> float:
    """Bytes of every array shape in an HLO instruction's text."""
    total = 0.0
    for dt, dims in _SHAPE.findall(text or ""):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def hlo_text(ev: Event) -> str:
    """The op's HLO instruction: its ``long_name``, or its name, which on
    a TPU trace is the instruction's text."""
    return str(ev.stats.get("long_name") or ev.name)


def kernel_of(ev: Event):
    text = hlo_text(ev)
    return next((k for k in KERNELS if k in text), None)


# -- loading -----------------------------------------------------------------

def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path) -> dict:
    """{"devices": {name: {"modules": [...], "ops": [...]}}, "host": [...]}"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        is_dev = re.fullmatch(r"/device:TPU:\d+", plane.name) is not None
        if not is_dev and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         dict(e.stats)) for e in line.events]
            if is_dev:
                d = devices.setdefault(plane.name, {"modules": [], "ops": []})
                if line.name == "XLA Modules":
                    d["modules"].extend(evs)
                elif line.name == "XLA Ops":
                    d["ops"].extend(evs)
            else:
                host.extend(evs)
    return {"devices": devices, "host": host}


# -- reduction ---------------------------------------------------------------

def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def label_gap(s: float, e: float, host: list) -> str:
    """What the host was doing in [s, e]: the shortest host event that
    covers at least half of it, named as a transfer (host-side
    linearisation and copies to or from the device), a dispatch (a jitted
    program's launch, by name where the event gives it), or else its own
    name; Python where no runtime event covers the gap."""
    best = None
    for h in host:
        if min(e, h.end) - max(s, h.start) >= 0.5 * (e - s) and \
                (best is None or h.dur < best.dur):
            best = h
    if best is None or best.name.startswith("$"):
        return "python"
    m = re.search(r"PjitFunction\((.*?)\)", best.name)
    if m:
        return f"dispatch {m.group(1)}"
    if re.search(r"Transfer|Transpose|Linearize|H2D|D2H|BufferFromPyval|"
                 r"device_put", best.name):
        return "transfer"
    if re.search(r"Execute|Dispatch", best.name):
        return "dispatch"
    return best.name


def device_span(raw: dict) -> tuple:
    """(first start, last end) of the programs (or, where the trace has
    none, the operations) that ran on any device."""
    evs = [e for d in raw["devices"].values()
           for e in (d["modules"] or d["ops"])]
    if not evs:
        raise ValueError("no operation ran on a device in the trace")
    return min(e.start for e in evs), max(e.end for e in evs)


def reduce(raw: dict) -> dict:
    """The numbers of one traced window, the span of the device's work."""
    devs = sorted(raw["devices"])
    if not devs:
        raise ValueError("the trace holds no TPU device")
    lo, hi = device_span(raw)
    window_s = hi - lo
    busy = []
    for name in devs:
        d = raw["devices"][name]
        evs = d["ops"] or d["modules"]
        busy.append(sum(b - a for a, b in
                        union([(e.start, e.end) for e in evs], lo, hi)))
    d0 = raw["devices"][devs[0]]
    programs, kernels = {}, {}
    for e in d0["modules"]:
        if lo <= e.start < hi:
            p = programs.setdefault(program_name(e.name),
                                    {"calls": 0, "seconds": 0.0})
            p["calls"] += 1
            p["seconds"] += e.dur
    for e in d0["ops"]:
        k = kernel_of(e) if lo <= e.start < hi else None
        if k is not None:
            kk = kernels.setdefault(k, {"calls": 0, "seconds": 0.0,
                                        "bytes": 0.0})
            kk["calls"] += 1
            kk["seconds"] += e.dur
            kk["bytes"] += hlo_bytes(hlo_text(e))
    merged = union([(e.start, e.end) for e in (d0["ops"] or d0["modules"])],
                   lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e - s >= MIN_GAP_S:
            gaps.append((label_gap(s, e, raw["host"]), e - s))
    return {"window_s": window_s, "busy_s": sum(busy) / len(busy),
            "n_devices": len(devs), "programs": programs,
            "kernels": kernels, "gaps": gaps}


def reduce_dir(trace_dir: Path) -> dict:
    return reduce(load(find_xplane(trace_dir)))


def breakdown(red: dict, top: int = 10) -> dict:
    """The device programs and kernels that took most time, and the
    longest idle gaps by host activity."""
    ops = [[k, v["seconds"]] for k, v in red["kernels"].items()]
    ops += [[k, v["seconds"]] for k, v in red["programs"].items()]
    ops.sort(key=lambda x: -x[1])
    gaps = sorted(([lab, s] for lab, s in red["gaps"]), key=lambda x: -x[1])
    return {"device_ops": ops[:top], "idle_gaps": gaps[:top]}
