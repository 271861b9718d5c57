"""The router's pad efficiency: rows answered over rows dispatched, 100
sum(used) / sum(batch) over the ``dpmm.serve.segment`` spans of the
traced window's requests (``programspans.engine_readings``). A count:
the same multiset of request sizes reads the same share.
"""


def read(ctx: dict):
    engine = ctx.get("engine")
    return engine["serve_pad_efficiency"] if engine else None
