"""Share of the traced window in which device 0's chip ran no operation
(1 - busy / window), in %: the device layer of a batch cell."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
