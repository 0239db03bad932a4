"""peak_bytes_in_use of the fullest chip, read after the window, in GB
(learner cells)."""


def read(rec):
    if "steps" not in rec.counts or not rec.memory_peak_bytes:
        return None
    return rec.memory_peak_bytes / 1e9
