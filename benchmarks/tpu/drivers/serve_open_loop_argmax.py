"""Open-loop serving, checked by how many served tokens the float32
reference ranks first.

The traffic, the clock, the end-to-end metrics and the traced record are
``serve_open_loop``'s; only the check differs.  ``serve_open_loop``
checks the widest gap of any served token below the reference's top
logit.  For a deep stack of sparse experts that maximum does not tell a
sound program from a lower precision: on seeded weights two experts'
routing scores often nearly tie, a bf16 rounding early in the stack
flips the choice, and the token's logits then move by a third of their
range whatever the precision (PERF.md, §6).  How often the served
token is not the reference's argmax does tell them apart, so this
driver checks ``argmax_miss``: that share, over the same seeded sample
of finished requests (the longest answer and three more).
"""
from __future__ import annotations

import os

import serving

HERE = os.path.dirname(os.path.abspath(__file__))


def run(run):
    import bench
    base = bench.load_module(os.path.join(HERE, "serve_open_loop.py"))
    seen = []
    served_gaps = serving.served_gaps

    def counted(*args, **kw):
        seen.append(served_gaps(*args, **kw))
        return seen[-1]

    # the base driver reads its served_gap limit; this driver reports
    # argmax_miss in its place
    limits = run.workload["check"]["limits"]
    run.workload = dict(run.workload, check=dict(
        run.workload["check"], limits=dict(limits, served_gap=None)))
    serving.served_gaps = counted
    try:
        res = base.run(run)
    finally:
        serving.served_gaps = served_gaps
    g = seen[-1]
    # nothing served to compare counts as every token missed
    miss = g["not_argmax"] / g["tokens"] if g["tokens"] else 1.0
    res["checks"] = [{"name": "argmax_miss", "value": miss,
                      "limit": limits["argmax_miss"]}]
    return res
