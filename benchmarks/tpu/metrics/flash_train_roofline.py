"""Share of its roofline that the flash kernels reach in training: the
least time for causal attention forward and backward of every traced
step, over the device time of the forward, delta, dQ and dKV kernels
(a forward recomputed under remat counts in the time, not in the work)."""
import trace_reduce
import work

#: the flash kernels' names in the device trace: the Pallas kernels'
#: function names, or the jitted wrappers in an operation's op_name
PATTERNS = [r"_flash_kernel", r"_bwd_delta_kernel", r"_bwd_dq_kernel",
            r"_bwd_dkv_kernel", r"flash_attention_fwd_pallas",
            r"flash_attention_bwd_pallas"]


def read(record):
    seconds = trace_reduce.kernel_seconds(record["trace"], PATTERNS)
    n = record.get("traced_steps", 0)
    if not seconds or not n:
        return None
    need = n * work.roofline_seconds(
        *work.flash_train(record["dims"], record["batch"], record["seq"]),
        record["peaks"])
    return 100.0 * need / seconds
