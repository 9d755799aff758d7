"""Context switches the Router logged inside the window, per credited step."""


def read(rec):
    n = rec.get("switches_in_window")
    if n is None or rec["credited_steps"] <= 0:
        return None
    return n / rec["credited_steps"]
