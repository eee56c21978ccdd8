"""Seconds per job in planning: self time of the program's ``plan`` span
(key profile and shard plan, ``api/facade.py``, ``balance/``)."""


def read(run):
    return run.self_s("plan")
