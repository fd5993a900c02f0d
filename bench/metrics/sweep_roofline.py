"""The sweeps' share of their roofline: the least time their work needs
(bench/work.py) over the device time of the solves in the traced
segment, in percent."""

import timeline
import work


def read(rec):
    spans = rec.traced("solve")
    if not spans or rec.sweep_work is None:
        return None
    flops, nbytes = rec.sweep_work
    sweeps = sum(info["sweeps"] for _, _, info in spans)
    need = sweeps * work.least_seconds(flops, nbytes, rec.device_kind)
    busy = timeline.busy(rec.trace, rec.trace_lo, rec.trace_hi,
                         within=[(a, b) for a, b, _ in spans])
    return 100.0 * need / busy if busy > 0 else None
