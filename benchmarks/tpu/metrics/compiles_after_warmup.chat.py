"""Compiles after warm-up: the program's ``compile`` spans of the run
(the tracer is attached once the engine has warmed up).  Read only from
a program whose engine step records its phases, as the one that also
records compiles does."""


def read(record):
    spans = record.get("spans", [])
    if not any(s["name"] == "admit" for s in spans):
        return None
    return sum(s["name"] == "compile" for s in spans)
