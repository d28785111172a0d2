"""Device milliseconds per batch of the pallas engine's batch
registration executable (``jit_pallas_batch``: the vmapped fixed-length
ICP scan with the NN kernel), from the profiler trace."""

MODULES = ("jit_pallas_batch",)


def read(ctx):
    t = ctx["trace"]
    if not t or not t["rounds"]:
        return None
    found = [ns for name, ns in t["modules"].items() if name in MODULES]
    if not found:
        return None
    return sum(found) / len(t["rounds"]) * 1e-6
