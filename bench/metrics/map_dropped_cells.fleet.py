"""Occupied map cells the submap capacity dropped, per frame of the
window's streams (``FrameDiagnostics.dropped_cells``, each stream's
running total): how far the maps overflow the configured capacity."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("frames"):
        return None
    return c["dropped_cells"] / c["frames"]
