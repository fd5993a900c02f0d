"""The serving dispatches' share of their roofline: the least time their
work needs (bench/work.py) over the device time of the pumps in the
traced segment, in percent."""

import timeline
import work


def read(rec):
    spans = [s for s in rec.traced("pump") if s[2].get("rows", 0) > 0]
    if not spans or rec.serve_work is None:
        return None
    need = sum(work.least_seconds(*rec.serve_work(info["rows"]), rec.device_kind)
               for _, _, info in spans)
    busy = timeline.busy(rec.trace, rec.trace_lo, rec.trace_hi,
                         within=[(a, b) for a, b, _ in spans])
    return 100.0 * need / busy if busy > 0 else None
