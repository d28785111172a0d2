"""The brute-force NN kernel's share of its roofline: the least time the
chip needs for the NN work of the window's pairs (each pair counted for
the iterations it ran, ``bench/counts/nn_search.py``; the larger of
operations over peak FLOP/s and bytes over peak bandwidth, a pair at a
time) over the device time of the kernel's custom calls
(``nn_search.<n> tpu_custom_call``) in the trace. None where no such
kernel ran."""

KERNEL = "nn_search"
CALL = "tpu_custom_call"


def read(ctx):
    t, calls = ctx["trace"], ctx["counters"].get("nn_calls")
    if not t or not calls:
        return None
    kernel_ns = sum(ns for name, ns in t["ops"].items()
                    if name.endswith(" " + CALL)
                    and name.split(" ", 1)[0].split(".", 1)[0] == KERNEL)
    if kernel_ns <= 0:
        return None
    harness = ctx["harness"]
    counts = harness.load_counts(KERNEL)
    peak = harness.peaks(ctx["device_kind"])
    least = sum(max(counts.flops(c) / peak["flops_per_s"],
                    counts.bytes_moved(c) / peak["hbm_bytes_per_s"])
                for c in calls)
    return 100.0 * least / (kernel_ns * 1e-9)
