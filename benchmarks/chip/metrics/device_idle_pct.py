"""Share of the traced window in which no operation ran on the device,
averaged over the chips: 100 (1 - busy / window). Busy is the union of
the intervals of the device plane's operations (chipbench/tracereduce.py).
"""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return trace["idle_pct"]
