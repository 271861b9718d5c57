"""The Gibbs iteration's share of the chip's roofline: the least time the
iteration's required work could take (counts/<family>.py at N, d and the
traced chunks' mean K_active, over peaks.json's peaks of the chips used)
over the device's busy time per iteration. ``bound`` says whether FLOPs
or bytes set the least time.
"""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or trace["busy_s"] <= 0 or ctx["iters_traced"] <= 0:
        return None
    work, peak, chips = ctx["work"], ctx["peaks"], ctx["chips"]
    t_flops = work["flops"] / (chips * peak["flops_per_s"])
    t_bytes = work["bytes"] / (chips * peak["hbm_bytes_per_s"])
    busy_per_iter = trace["busy_s"] / ctx["iters_traced"]
    return {"value": 100.0 * max(t_flops, t_bytes) / busy_per_iter,
            "bound": "flops" if t_flops > t_bytes else "bytes"}
