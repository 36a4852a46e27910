"""Share of its roofline that the int8 decode-attention kernel reaches:
the least time the chip needs for the traced decode rounds (the int8 K/V
and scales of each live slot's length, every layer) over the kernel's
device time."""
import trace_reduce
import work

#: the kernel's names in the device trace: the Pallas kernel's function
#: name, or its jitted wrapper in an operation's op_name
PATTERNS = [r"_flash_decode_kernel", r"flash_decode_pallas"]


def read(record):
    seconds = trace_reduce.kernel_seconds(record["trace"], PATTERNS)
    steps = [s["decode"] for s in record.get("traced_steps", [])
             if s["decode"]]
    if not seconds or not steps:
        return None
    need = sum(work.roofline_seconds(*work.kvq_decode(record["dims"], ls),
                                     record["peaks"]) for ls in steps)
    return 100.0 * need / seconds
