"""Seconds per job building the public result: self time of the program's
``to_outcome`` span, the frozensets of (lo, hi) pairs made from the packed
pair arrays (``api/runners.py``, ``PackedOutcome.to_outcome``).  Nothing
is read where the program has no such span."""


def read(run):
    return run.self_s("to_outcome")
