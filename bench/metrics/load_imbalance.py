"""Max over mean of the per-shard loads the program reports
(``ERResult.blocking.load``): an exact count, 1.0 when level."""


def read(run):
    load = run.result["load"]
    if not load or sum(load) == 0:
        return None
    return max(load) * len(load) / sum(load)
