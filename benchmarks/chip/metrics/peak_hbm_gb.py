"""``peak_bytes_in_use`` of the fullest chip after the window, before the
reference runs, in GB (1e9 bytes)."""


def read(run):
    return run.c["memory_peak_bytes"] / 1e9
