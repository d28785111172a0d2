"""Share of the batch executable's pair-iterations spent on pairs that
had already converged: 100 x (1 - pair_iterations / pair_iterations_run),
from the engine's ``report()`` counters over the window. None where the
program has no such counters."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("pair_iterations_run"):
        return None
    return 100.0 * (1.0 - c["pair_iterations"] / c["pair_iterations_run"])
