"""Device busy seconds inside UPDATE_ACTOR operations, per credited step."""
from bench.trace_reduce import busy_in


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr["busy"] or rec["credited_steps"] <= 0:
        return None
    return busy_in(tr, "UPDATE_ACTOR") / rec["credited_steps"]
