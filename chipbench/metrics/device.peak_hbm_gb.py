"""``peak_bytes_in_use`` after the window on the fullest chip, in GB."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 1e9
