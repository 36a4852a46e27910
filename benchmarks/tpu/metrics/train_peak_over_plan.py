"""The allocator's peak (``peak_bytes_in_use``) over the planner's
predicted peak: its activation plan plus the parameters and AdamW state."""


def read(record):
    plan = record.get("plan_bytes", 0) + record.get("state_bytes", 0)
    peak = record.get("memory_peak_bytes", 0)
    return 100.0 * peak / plan if plan and peak else None
