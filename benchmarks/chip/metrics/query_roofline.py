"""The serving query step's share of the chip's roofline: the least time
the rows answered in the traced window need (``counts/<family>.py``
``query_work``: their log densities and answers, over ``peaks.json``'s
peaks) over the device's busy time in that window. ``bound`` says
whether FLOPs or bytes set the least time.
"""


def read(ctx: dict):
    trace, work = ctx.get("trace"), ctx.get("query_work")
    if not trace or not work or trace["busy_s"] <= 0:
        return None
    peak, chips = ctx["peaks"], ctx["chips"]
    t_flops = work["flops"] / (chips * peak["flops_per_s"])
    t_bytes = work["bytes"] / (chips * peak["hbm_bytes_per_s"])
    return {"value": 100.0 * max(t_flops, t_bytes) / trace["busy_s"],
            "bound": "flops" if t_flops > t_bytes else "bytes"}
