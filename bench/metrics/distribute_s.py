"""Seconds per job placing the mapper splits on the mesh, one per chip:
self time of the program's ``distribute`` span, which blocks on the
placement when traced (``api/runners.py``, ``ShardMapRunner.run_raw``).
Nothing is read where the program has no such span, as on one chip, where
the shards are vmapped."""


def read(run):
    return run.self_s("distribute")
