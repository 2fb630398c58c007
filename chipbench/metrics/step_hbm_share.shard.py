"""The sharded step program's share of one chip's HBM bandwidth, in %:
the bytes device 0's shard needs, one read and one write of the live
compact cells it holds per simulated step (2 x its live cells, which
the program reports as the gauge `dist.shard_live_cells{shard="0"}`,
x channels x state bytes x steps, summed over the requests completed in
the window), over device 0's device time in the step programs times the
peak bandwidth of `peaks.json`. The trace gives the step programs' time
per device as the mean over the devices; the one program runs on all of
them at once and its exchanges keep them in step. Nothing is read where
the program reports no such gauge."""


def _shard0_live_cells(snapshot):
    for g in (snapshot or {}).get("gauges", []):
        if (g["name"] == "dist.shard_live_cells"
                and g["labels"].get("shard") == "0"):
            return g["value"]
    return None


def read(ctx):
    if ctx.trace is None or ctx.trace["step_s"] <= 0:
        return None
    live = _shard0_live_cells(ctx.obs)
    done = [q for q in ctx.requests if q.ok]
    if not live or not done:
        return None
    need = sum(2 * live * ctx.channels * ctx.itemsize * q.steps
               for q in done)
    return 100.0 * need / (ctx.trace["step_s"] * ctx.peaks["hbm_bytes_per_s"])
