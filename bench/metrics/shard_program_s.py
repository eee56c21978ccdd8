"""Seconds per job in the device shard program, dispatch to ready: the
program's ``shard_program`` span, which blocks on the device when traced
(``api/runners.py``, ``api/variants.py``, ``core/``)."""


def read(run):
    return run.total_s("shard_program")
