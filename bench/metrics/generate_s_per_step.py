"""Device busy seconds inside GENERATE operations, per credited step."""
from bench.trace_reduce import busy_in


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr["busy"] or rec["credited_steps"] <= 0:
        return None
    return busy_in(tr, "GENERATE") / rec["credited_steps"]
