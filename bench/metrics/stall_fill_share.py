"""Share of the verifier's stall time during which the chip was busy (%).

The verifier stalls a step between its GENERATE and its UPDATE_ACTOR. A
plane that fills the stall with another job's work keeps the chip busy
through it; one that blocks on it leaves the chip idle.
"""
from bench.trace_reduce import busy_in, total


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr["busy"]:
        return None
    stall = total(tr["spans"].get("verifier", []))
    if stall <= 0:
        return None
    return 100.0 * busy_in(tr, "verifier") * 1e9 / stall
