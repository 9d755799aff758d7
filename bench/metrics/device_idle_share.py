"""Share of the window in which no operation ran on the chip (%), from
the profiler trace, averaged over chips."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr["busy"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
