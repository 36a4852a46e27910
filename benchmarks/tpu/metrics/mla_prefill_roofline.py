"""Share of its roofline that the flash forward kernel reaches in latent
attention's prefill: the least time for causal multi-head attention over
each traced prefill's real prompt tokens (head dims ``qk_nope + qk_rope``
and ``v_head_dim``), every layer, over the flash forward kernel's device
time in the stretch."""
import trace_reduce
import work
import work_mla

#: the flash forward kernel's names in the device trace
PATTERNS = [r"_flash_kernel", r"flash_attention_fwd_pallas"]


def read(record):
    seconds = trace_reduce.kernel_seconds(record["trace"], PATTERNS)
    prompts = [n for s in record.get("traced_steps", [])
               for n in s["prefill"]]
    if not seconds or not prompts:
        return None
    need = sum(work.roofline_seconds(
        *work_mla.mla_prefill(record["dims"], n), record["peaks"])
        for n in prompts)
    return 100.0 * need / seconds
