"""Share of the serving window in which no operation ran on the device:
100 (1 - busy / window), busy the union of the device plane's operation
intervals (chipbench/tracereduce.py). It says how far the host, not the
chip, sets the latency of a query.
"""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0 or "query_work" not in ctx:
        return None
    return trace["idle_pct"]
