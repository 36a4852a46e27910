"""Model FLOP utilization of the traced stretch: the FLOPs the traced
engine steps require (each prefill over its real prompt tokens with one
LM-head row, each decoded token over its cache length) over the
stretch's length times the chip's bf16 peak."""
import work


def read(record):
    return work.mfu_percent(
        work.served_flops(record["dims"], record.get("traced_steps", [])),
        record)
