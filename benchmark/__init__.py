"""The quest_tpu benchmark: one cell per run of ``benchmark/run.py``."""
