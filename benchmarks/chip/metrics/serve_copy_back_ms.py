"""The serving engine's copies of its answers to the host, per request
answered: the total of its ``dpmm.serve.copy_back`` spans in the traced
window (``programspans.engine_readings``). The first copy of a segment
also holds the wait for its step. ``by_out_ms`` splits the time by
output; ``bytes_per_request`` is what the copies carried.
"""


def read(ctx: dict):
    engine = ctx.get("engine")
    return engine["serve_copy_back_ms"] if engine else None
