"""Seconds of context switching per credited step: the benchmark's span
around each switch, from its entry until the loaded state is on the chip,
clipped to the window."""
from bench.trace_reduce import total


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("switches_in_window") is None \
            or rec["credited_steps"] <= 0:
        return None
    return total(tr["spans"].get("switch", [])) / 1e9 / rec["credited_steps"]
