"""Share of the traced window in which no operation ran on the device, in a
training cell: 1 - busy/window from the profiler trace. The gaps, each with
the ``train.*`` span that covered it, are in the result's breakdown."""


def read(run):
    t = run.layer.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
