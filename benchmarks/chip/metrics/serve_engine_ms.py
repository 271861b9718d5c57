"""The serving engine's host time per request answered: the total of its
``dpmm.serve.query`` spans in the traced window over the requests
answered (``programspans.engine_readings``). ``self_ms`` splits it by
span name, each span less its child spans; the parts add up to it.
"""


def read(ctx: dict):
    engine = ctx.get("engine")
    return engine["serve_engine_ms"] if engine else None
