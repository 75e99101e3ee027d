"""Share of the traced window in which no operation ran on the device, in
percent: 1 - busy / window from the profiler trace."""


def read(ctx):
    t = ctx.trace
    if not t["devices_busy"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
