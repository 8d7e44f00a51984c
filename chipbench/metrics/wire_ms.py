"""Host milliseconds per round of the wire: the program's
``wire.collect`` (uploads to the host), ``wire.encode`` (attack payloads,
encode, CRC, send) and ``wire.decode`` (decode, CRC check, retry, late
sort) spans (``chipbench/spans.py``)."""

from chipbench import spans as S


def read(ctx):
    return S.per_round_ms(ctx, ("wire.collect", "wire.encode",
                                "wire.decode"))
