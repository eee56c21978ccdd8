"""Peak device memory after the window: ``peak_bytes_in_use`` of the
fullest chip."""


def read(run):
    return run.peak_bytes
