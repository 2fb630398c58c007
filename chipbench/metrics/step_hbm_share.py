"""The step program's share of the chip's HBM bandwidth, in %: the bytes
the simulation needs, one read and one write of the live compact state
per simulated step (2 x live cells x channels x state bytes x steps,
summed over the requests completed in the window; holes and halos are
not counted), over the device time of the step programs times the peak
bandwidth of `peaks.json`. A temporally fused kernel that keeps several
steps on chip needs fewer bytes than this counts, and could read above
100% near peak bandwidth."""


def read(ctx):
    if ctx.trace is None or ctx.trace["step_s"] <= 0:
        return None
    done = [q for q in ctx.requests if q.ok]
    if not done:
        return None
    need = sum(2 * ctx.live_cells(q.r) * ctx.channels * ctx.itemsize
               * q.steps for q in done)
    return 100.0 * need / (ctx.trace["step_s"] * ctx.peaks["hbm_bytes_per_s"])
