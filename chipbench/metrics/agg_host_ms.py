"""Host milliseconds per round of the server aggregation's host side, the
self time of the program's ``agg.host`` span: upload norms, densifying,
the aggregator's update and finalize launches (``chipbench/spans.py``)."""

from chipbench import spans as S


def read(ctx):
    return S.per_round_ms(ctx, ("agg.host",), field="self_s")
