"""Operations and bytes of the brute-force NN kernel
(``repro.kernels.nn_search``) for one registration pair, counted for
the iterations it ran (``call``: valid source points ``n``, valid target
points ``m``, ``iterations``). Sizes are valid points, never padding, so
the same work counts the same whatever implements the search.

An iteration scores every source point against every target point: the
8-term augmented inner product (DESIGN.md §2), 8 multiplies and 8 adds a
candidate. The (8, m) float32 augmented target is read once a pair; an
iteration reads the (8, n) augmented source and writes a best and a
runner-up score and index, 16 bytes a query."""

AUG_ROWS = 8
FLOAT = 4


def flops(call: dict) -> float:
    return 2.0 * AUG_ROWS * call["n"] * call["m"] * call["iterations"]


def bytes_moved(call: dict) -> float:
    per_iteration = AUG_ROWS * call["n"] * FLOAT + 16 * call["n"]
    return (AUG_ROWS * call["m"] * FLOAT
            + per_iteration * call["iterations"])
