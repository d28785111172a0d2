"""Device milliseconds per service round of the vmapped scrub and voxel
downsample (``prepare_scans``), from the profiler trace."""

MODULES = ("jit_prepare_scans",)


def read(ctx):
    t = ctx["trace"]
    if not t or not t["rounds"]:
        return None
    found = [ns for name, ns in t["modules"].items() if name in MODULES]
    if not found:
        return None
    return sum(found) / len(t["rounds"]) * 1e-6
