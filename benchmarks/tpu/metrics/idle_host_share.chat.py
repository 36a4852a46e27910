"""Share of the traced stretch in which the device idled while the
engine step did host work: idle gaps labelled by the step itself or by
one of its phases other than ``sync`` (``admit``, ``dispatch``,
``emit``), from the program's spans in the profiler trace."""

#: the engine step's host-side phases, and the step span itself
HOST = ("step", "admit", "dispatch", "emit")


def read(record):
    t = record["trace"]
    if not any(s["name"] == "step" for s in t.get("program_spans", [])):
        return None
    idle = sum(sec for sec, label in t["program_gaps"] if label in HOST)
    return 100.0 * idle / t["n_devices"] / t["window_s"]
