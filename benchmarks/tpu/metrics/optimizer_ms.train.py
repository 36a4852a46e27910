"""Device time per traced training step of AdamW (the ``optimizer``
scope: the gradient norm and clip, the moments and the update)."""
import program_trace


def read(record):
    seconds = program_trace.scope_seconds(record["trace"], "optimizer")
    n = record.get("traced_steps", 0)
    return 1e3 * seconds / n if seconds and n else None
