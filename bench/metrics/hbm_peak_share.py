"""Peak device memory in use over the run, as a share of the chip's HBM
in ``bench/peaks.json`` (%)."""


def read(rec):
    peak = rec.get("memory_peak_bytes")
    if not peak:
        return None
    return 100.0 * peak / rec["hbm_bytes"]
