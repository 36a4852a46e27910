"""Share of its roofline that the latent decode kernel reaches: the least
time the chip needs for the traced decode rounds (the bf16 latent and
rope key of every position that each round's live slots read, every
layer, by the engine's ``latent_positions`` counter on its ``step``
spans) over the kernel's device time."""
import trace_reduce
import work
import work_mla

#: the kernel's names in the device trace: the Pallas kernel's function
#: name, or its name in an operation's op_name
PATTERNS = [r"_mla_decode_kernel", r"mla_decode_pallas"]


def read(record):
    seconds = trace_reduce.kernel_seconds(record["trace"], PATTERNS)
    steps = record.get("traced_steps", [])
    if not seconds or not steps:
        return None
    lo, hi = steps[0]["t0"], steps[-1]["t1"]
    rounds = [s["attrs"] for s in record.get("spans", [])
              if s["name"] == "step" and "latent_positions" in s["attrs"]
              and lo <= s["start"] and s["end"] <= hi]
    if not rounds:
        return None
    need = sum(work.roofline_seconds(
        *work_mla.mla_decode(record["dims"], a["latent_positions"],
                             a["occupancy"]), record["peaks"])
        for a in rounds)
    return 100.0 * need / seconds
