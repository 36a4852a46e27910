"""Median engine step that admitted nothing (a decode round alone), from
the program's ``step`` spans with ``admitted=0``."""
import traffic


def read(record):
    pauses = record.get("pauses", [])
    steps = [s["end"] - s["start"] for s in record.get("spans", [])
             if s["name"] == "step" and s["attrs"].get("admitted") == 0
             and not any(s["start"] < b and s["end"] > a for a, b in pauses)]
    return traffic.percentile(steps, 50) * 1e3 if steps else None
