"""Share of the tile kernels' roofline over the traced window.

For each tile task dispatched in the window, the least time the chip could
take is the larger of its flops over the bf16 peak and its bytes over the
HBM bandwidth (``bench/flops.py``; the tiles are f32 at full precision, for
which no peak is published, so the bf16 peak stands in and the share reads
low). Their sum over the device's busy time in the window is the share; the
bound that sets most of the least time is logged.
"""
import sys

from bench import flops


def read(record):
    trace = record.get("trace")
    c = record["counters"]
    if trace is None or trace["cut"] or not c.get("tasks_by_kind") or trace["busy_s"] <= 0:
        return None
    peaks = record["peaks"]
    least = {"compute": 0.0, "memory": 0.0}
    for kind, n in c["tasks_by_kind"].items():
        t, bound = flops.least_seconds(kind, c["tile"], c["itemsize"],
                                       peaks["bf16_flops"], peaks["hbm_bytes_per_s"])
        least[bound] += n * t
    print(f"tile_roofline: least time {least} s bound by compute/memory; "
          f"device busy {trace['busy_s']!r} s", file=sys.stderr)
    return 100.0 * (least["compute"] + least["memory"]) / trace["busy_s"]
