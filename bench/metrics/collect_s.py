"""Seconds per job in host collection: self time of the program's
``collect`` span (band transfer and packed pair dedup, ``api/results.py``,
``variants.collect``)."""


def read(run):
    return run.self_s("collect")
