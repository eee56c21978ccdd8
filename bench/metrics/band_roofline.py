"""Share of the band work's least time in the device-busy time inside the
``shard_program`` span: 100 * least_time / busy.  The least time is what
the chip's published peaks allow for the work any band engine must do
(``bench/roofline.py``).  Nothing is read where the trace has no device
plane or no device time inside the span."""
from bench import roofline


def read(run):
    t = run.trace
    if t is None:
        return None
    busy = t["busy_in_s"].get("shard_program", 0.0)
    if busy <= 0:
        return None
    least, _ = roofline.least_time(run.work, run.device_kind)
    return 100.0 * least * run.jobs / busy
