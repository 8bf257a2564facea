"""% of the GAT attention kernel's roofline in the traced part: the least
time its traced passes need on this chip (`bench/lib/gat_work.py`) over
the kernel's summed device time."""
from bench.lib import gat_work
from bench.lib.peaks import Peaks


def read(record):
    trace, traced = record.get("trace"), record.get("traced")
    if (not trace or not traced or "attn_passes" not in traced
            or trace["kernel_s"].get("gat_attn", 0.0) <= 0):
        return None
    peaks = Peaks(**record["peaks"])
    least = sum(gat_work.attn_min_seconds(*p, peaks)[0]
                for p in traced["attn_passes"])
    return 100.0 * least / trace["kernel_s"]["gat_attn"]
