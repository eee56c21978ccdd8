"""Seconds per job of collective operations on the device (all-to-all
shuffle, halo collective-permute, and the small reductions), summed
durations averaged over the chips.  Nothing is read where the trace shows
no collective operation, as on one chip, where the shards are vmapped."""


def read(run):
    t = run.trace
    if t is None or not t["collective_ops"]:
        return None
    return t["collective_s"] / run.jobs
