"""Host milliseconds per round the main thread waits for the worker's
package, the program's ``round.wait_prefetch`` span: the producer's queue
wait (``chipbench/spans.py``)."""

from chipbench import spans as S


def read(ctx):
    return S.per_round_ms(ctx, ("round.wait_prefetch",))
