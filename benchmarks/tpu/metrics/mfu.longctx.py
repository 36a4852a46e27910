"""Model FLOP utilization of the traced stretch of a latent-attention,
held-expert serving cell: the FLOPs the traced engine steps require (each
prefill over its real prompt tokens with one LM-head row, each decoded
token over its cache length, held experts at their expected share) over
the stretch's length times the chip's bf16 peak."""
import work
import work_mla


def read(record):
    return work.mfu_percent(
        work_mla.served_flops(record["dims"],
                              record.get("traced_steps", [])), record)
