"""Share of the traced serving window in which the engine was inside a
request (``dpmm.serve.query``) and no operation ran on the device,
averaged over the chips it used, with compilation left out
(``programspans.engine_idle``). ``by_span`` gives that idle time in ms
by the innermost span covering it: where the host held the chip back
while a request was being served.
"""


def read(ctx: dict):
    engine = ctx.get("engine")
    return engine["engine_idle_pct"] if engine else None
