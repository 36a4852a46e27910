"""Model FLOP utilization of the traced steps: forward and backward
FLOPs they require (three times the forward, causal attention at its
half, no recompute) over the stretch's length times the bf16 peak."""
import work


def read(record):
    n = record.get("traced_steps", 0)
    return work.mfu_percent(
        n * work.train_flops(record["dims"], record.get("batch", 0),
                             record.get("seq", 0)), record)
