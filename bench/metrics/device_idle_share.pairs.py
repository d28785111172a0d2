"""Share of the traced pairs window in which no operation ran on the
chip: 100 x (1 - busy / window), from the profiler trace."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_ns"] / t["window_ns"])
