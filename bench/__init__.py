"""The benchmark: served GRPO jobs on TPU chips (see ``bench/run.py``)."""
