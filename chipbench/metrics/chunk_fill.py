"""Share of the executed tier-chunk rows that hold a participant, not
padding: the ``valid`` over the ``rows`` counts of the program's
``exec.dispatch`` spans (``chipbench/spans.py``)."""

from chipbench import spans as S


def read(ctx):
    s = S.of(ctx)["spans"].get("exec.dispatch")
    if s is None or not s["counts"].get("rows"):
        return None
    return 100.0 * s["counts"].get("valid", 0) / s["counts"]["rows"]
