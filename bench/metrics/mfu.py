"""Model FLOPs of the credited steps over the window's peak FLOPs (%).

The FLOPs are the model's (``bench/flops.py``), credited like the steps;
the peak is the bf16 peak of ``bench/peaks.json`` times the chips."""


def read(rec):
    if rec.get("flops") is None or rec["window_s"] <= 0:
        return None
    return 100.0 * rec["flops"] / (rec["window_s"] * rec["chips"]
                                   * rec["peak_flops"])
