"""Host milliseconds per service round: the benchmark's ``bench.round``
span around ``submit``, ``step()`` and ``sync()``, less the time inside
it in which the chip was busy. From the profiler trace."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["rounds"]:
        return None
    host = [r["end"] - r["start"] - r["device_busy"] for r in t["rounds"]]
    return sum(host) / len(host) * 1e-6
