"""Seconds per job fetching the shard program's output to the host: self
time of the program's ``transfer`` span inside ``collect``, one
``jax.device_get`` of the bands, eids and counters that collection reads
(``api/runners.py``, ``results.collected_leaves``).  Nothing is read
where the program has no such span."""


def read(run):
    return run.self_s("transfer")
