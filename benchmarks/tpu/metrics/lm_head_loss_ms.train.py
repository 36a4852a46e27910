"""Device time per traced training step of the LM head and its cross
entropy (the ``lm_head_loss`` scope), forward and backward."""
import program_trace


def read(record):
    seconds = program_trace.scope_seconds(record["trace"], "lm_head_loss")
    n = record.get("traced_steps", 0)
    return 1e3 * seconds / n if seconds and n else None
