"""Share of device 0's idle time in the traced window that no program
span below ``round`` on the main thread covers (``chipbench/spans.py``):
the idle time the trace cannot yet name."""

from chipbench import spans as S


def read(ctx):
    sp = S.of(ctx)
    if not sp["main"] or sp["idle_s"] <= 0:
        return None
    return 100.0 * (sp["idle_s"] - sp["idle_attributed_s"]) / sp["idle_s"]
