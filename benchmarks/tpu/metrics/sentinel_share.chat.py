"""Share of the device's busy time spent in the decode program's fused
health sentinel (the ``sentinel`` scope: finite logits, sampled token in
the vocabulary, a scattered prompt).  Read only from a decode program
that names its phases (an ``lm_head`` scope); there a sentinel fused
into operations named by another scope reads 0."""
import program_trace


def read(record):
    t = record["trace"]
    if not program_trace.scope_seconds(t, "lm_head"):
        return None
    return 100.0 * program_trace.scope_seconds(t, "sentinel") / t["busy_s"]
