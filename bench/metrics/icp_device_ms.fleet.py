"""Device milliseconds per service round of the ICP executables: the
slot engine's fleet registration and the recovery tiers' registrations,
which the trace names alike (``jit_run``), from the profiler trace."""

MODULES = ("jit_run",)


def read(ctx):
    t = ctx["trace"]
    if not t or not t["rounds"]:
        return None
    found = [ns for name, ns in t["modules"].items() if name in MODULES]
    if not found:
        return None
    return sum(found) / len(t["rounds"]) * 1e-6
