"""Operations and bytes of the round's Pallas kernels, and the chip's peaks.

A kernel's least time is the larger of its bytes over the chip's HBM
bandwidth and its operations over the chip's peak. The bytes are those of
its operands and results as the HLO instruction declares them (the
lane-dense ``(rows, 128)`` tiles, padding included), read from the trace
by ``chipbench/trace.py``; ``tile_elements`` and ``call_bytes`` give the
same count from a vector's length, for the tests. The operations are the
elementwise work the algorithm needs per element, which at these
intensities never sets the bound: all three kernels are bound by HBM.
"""
from __future__ import annotations

import json
from pathlib import Path

LANES, ROW_TILE, MAX_BLOCK_ROWS = 128, 32, 512
N_BINS = 256

# per element: bytes moved, operations needed
#   magnitude_histogram: read |x| (f32), bin it (abs, scale, floor, count)
#   hybrid_compress: read x (f32); write kept (f32) and sign (i8); compare,
#     select, sign, three reductions
#   recover: read kept (f32), sign (i8), local (f32); write f32; sign test,
#     magnitude test, two selects, one multiply
PER_ELEMENT = {
    "magnitude_histogram": {"bytes": 4, "ops": 4},
    "hybrid_compress": {"bytes": 9, "ops": 7},
    "recover": {"bytes": 13, "ops": 7},
}


def peaks(device_kind: str) -> dict:
    """The chip's peaks by ``device_kind``; an unknown chip is an error."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "chipbench/peaks.json")
    return table[device_kind]


def tile_elements(n: int) -> int:
    """Elements of an ``n``-vector in the kernels' padded tile layout."""
    rows = -(-n // LANES)
    block = min(MAX_BLOCK_ROWS, -(-rows // ROW_TILE) * ROW_TILE)
    per_block = block * LANES
    return -(-n // per_block) * per_block


def call_bytes(kernel: str, n: int, batch: int = 1) -> int:
    """Bytes one call moves for ``batch`` vectors of ``n`` elements."""
    e = tile_elements(n) * batch
    extra = {"magnitude_histogram": N_BINS * LANES * 4 * batch + 4,
             "hybrid_compress": 0, "recover": 0}[kernel]
    return PER_ELEMENT[kernel]["bytes"] * e + extra


def least_seconds(kernel: str, nbytes: float, pk: dict) -> float:
    ops = PER_ELEMENT[kernel]["ops"] * nbytes / PER_ELEMENT[kernel]["bytes"]
    return max(nbytes / pk["hbm_bytes_per_s"], ops / pk["bf16_flops_per_s"])


def roofline_reader(kernel: str):
    """The reader of ``<kernel>_roofline``: the least time the kernel's
    calls in the traced window could take over their device time, in %;
    nothing where the trace holds no call of it."""
    def read(ctx):
        k = ctx["trace"]["kernels"].get(kernel)
        if not k or k["seconds"] <= 0 or k["bytes"] <= 0:
            return None
        pk = peaks(ctx["device"]["kind"])
        return 100.0 * least_seconds(kernel, k["bytes"], pk) / k["seconds"]
    return read
