"""Engine prefill time per thousand prompt tokens: the program's
``prefill`` spans (from admission to the first token: the bucketed
forward pass, the scatter into the slot pool, the first token's sampling
and its sync) over the real prompt tokens they held; spans that overlap
the profiler's start or stop are left out."""


def read(record):
    pauses = record.get("pauses", [])
    spans = [s for s in record.get("spans", [])
             if s["name"] == "prefill" and "plen" in s["attrs"]
             and not any(s["start"] < b and s["end"] > a for a, b in pauses)]
    tokens = sum(s["attrs"]["plen"] for s in spans)
    if not tokens:
        return None
    return sum(s["end"] - s["start"] for s in spans) / tokens * 1e6
