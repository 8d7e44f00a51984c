"""Host milliseconds per round of the executor's launch loop, the self
time of the program's ``exec.dispatch`` span: chunk inputs to the device,
the tier-chunk launches, the fold (``chipbench/spans.py``)."""

from chipbench import spans as S


def read(ctx):
    return S.per_round_ms(ctx, ("exec.dispatch",), field="self_s")
