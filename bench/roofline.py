"""Chip peaks and the least time the band work needs on them.

``PEAKS`` holds the published peaks of each chip by JAX's ``device_kind``;
a kind that is not in the table is an error, never a default.

``band_work`` counts the work any band engine has to do for one resolve,
whichever engine implements it: the cheap matchers on every blocked slot
(no padding slots), the expensive matcher on every pair the skip rule
cannot drop, and every record read once from HBM.  The least time
is the larger of the operation bound and the byte bound; a share of it
cannot pass 100% while the device time is at least that least time.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect
    "TPU v5 lite": {"flops": 197e12, "int_ops": 393e12, "hbm_bytes_s": 819e9,
                    "ici_bits_s": 1600e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/roofline.py with their source")
    return PEAKS[device_kind]


def blocked_slots(n: int, w: int) -> int:
    """Pairs at sorted distance 1 .. w-1 among n records."""
    return sum(max(n - d, 0) for d in range(1, w))


# operations per pair of one matcher on a field of width k:
# (float ops, integer ops)
MATCHER_OPS = {
    # dot product, shift and scale
    "cosine": lambda k: (2 * k + 2, 0),
    # and, or, two popcounts, two sums per word; one divide
    "jaccard": lambda k: (1, 6 * k),
    # 6 integer ops per cell of the k x k table, 2k to count the lengths;
    # divide and subtract to finish the score
    "edit": lambda k: (4, 6 * k * k + 2 * k),
}


def band_work(*, n: int, w: int, matcher: dict, widths: dict,
              row_bytes: int, survivors: int) -> dict:
    """Operations and bytes one resolve requires (see module doc).

    ``matcher`` is the configuration's cascade, ``widths`` maps each field
    to its shape per record, ``row_bytes`` is one record's bytes (key, eid
    and payload).  Every matcher but the most expensive runs on every
    blocked slot, with 6 float ops to weight, sum and gate; the most
    expensive runs on the ``survivors``, the slots the skip rule cannot
    drop."""
    slots = blocked_slots(n, w)
    ms = sorted(matcher["matchers"], key=lambda m: m["cost"])
    flops = slots * 6
    int_ops = 0
    for i, m in enumerate(ms):
        k = int(widths[m["field"]][-1]) if widths[m["field"]] else 1
        f, o = MATCHER_OPS[m["kind"]](k)
        count = survivors if i == len(ms) - 1 and len(ms) > 1 else slots
        flops += count * f
        int_ops += count * o
    return {"slots": slots, "survivors": survivors, "flops": flops,
            "int_ops": int_ops, "bytes": n * row_bytes}


def least_time(work: dict, device_kind: str):
    """(seconds, bound) with bound "ops" or "bytes": the larger of the
    time the peaks allow for the operations and for the bytes."""
    pk = peaks(device_kind)
    t_ops = work["flops"] / pk["flops"] + work["int_ops"] / pk["int_ops"]
    t_bytes = work["bytes"] / pk["hbm_bytes_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
