"""Host milliseconds per round of the worker's producer step, the
program's ``prefetch`` span: participant draw, plan, fault draw, batch
gather for the next round (``chipbench/spans.py``)."""

from chipbench import spans as S


def read(ctx):
    return S.per_round_ms(ctx, ("prefetch",))
