"""Seconds per job of attempt host work: self time of the program's
``attempt`` span, outside ``shard_program`` and ``collect``.  It covers
plan application and shard input before the device program, and the
materialisation of the public pair sets after collection
(``api/runners.py``, ``PackedOutcome.to_outcome``)."""


def read(run):
    return run.self_s("attempt")
