"""95th percentile of the scheduler's queue wait, from the program's
``queue`` spans (submit to admission) of the traced run; spans that
overlap the profiler's start or stop are left out."""
import traffic


def read(record):
    pauses = record.get("pauses", [])
    waits = [s["end"] - s["start"] for s in record.get("spans", [])
             if s["name"] == "queue"
             and not any(s["start"] < b and s["end"] > a for a, b in pauses)]
    return traffic.percentile(waits, 95) * 1e3 if waits else None
