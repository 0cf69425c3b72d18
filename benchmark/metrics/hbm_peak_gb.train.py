"""hbm_peak_gb.train: ``memory_stats()["peak_bytes_in_use"]`` of the
fullest chip, read after the window and before the reference runs."""


def read(run):
    if run.device["platform"] != "tpu" or not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 1e9
