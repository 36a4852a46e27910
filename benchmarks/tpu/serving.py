"""What the serving drivers share: the engine as a deployment builds it,
a client that follows every request on the wall clock, and the check of
served tokens against the plain reference.

The client calls only the engine's public surface: ``submit``, ``step``
and ``request_states``.  After each ``step`` it reads every open
request's token count; a token counts as delivered when the ``step`` that
produced it returns.  Each step's work (prompts prefilled, and the cache
length of every decoded token) is derived from those counts, so the
required-work functions in ``work.py`` can be applied to it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import traffic

TERMINAL = ("DONE", "CANCELLED", "DROPPED", "FAILED", "MIGRATED")


def build_engine(run):
    """Seeded weights in the served dtype, the engine, and its warm-up."""
    import jax.numpy as jnp
    from repro.serve import ServeEngine

    eng = run.workload["engine"]
    params = run.reference.make_params(run.config, run.seed,
                                       jnp.dtype(run.config["weight_dtype"]))
    engine = ServeEngine(
        params, run.program_config(), max_slots=eng["max_slots"],
        max_len=eng["max_len"], prompt_buckets=tuple(eng["prompt_buckets"]),
        policy_name=eng["policy"],
        max_prefill_per_step=eng["max_prefill_per_step"], temperature=0.0,
        seed=0)
    engine.warmup()
    return engine, params


@dataclasses.dataclass
class Tracked:
    req: traffic.Request
    rid: int = -1
    due_at: float = 0.0   # the clock reading at which it fell due
    times: list = dataclasses.field(default_factory=list)
    state: str = "QUEUED"


class Client:
    """Submits requests and follows them through ``engine.step()``."""

    def __init__(self, run, engine):
        self.run, self.engine = run, engine
        self.open: dict[int, Tracked] = {}
        self.steps: list[dict] = []     # per step: t0, t1, prefill, decode
        self.lateness: list[float] = []

    def submit(self, tr: Tracked, now: float, due_at: float) -> None:
        with self.run.annotate("submit"):
            tr.rid = self.engine.submit(tr.req.prompt, tr.req.max_new_tokens)
        tr.due_at = due_at
        self.lateness.append(now - due_at)
        self.open[tr.rid] = tr

    def step(self) -> dict:
        t0 = self.run.clock()
        with self.run.annotate("step"):
            self.engine.step()
        t1 = self.run.clock()
        with self.run.annotate("request_states"):
            states = self.engine.request_states()
        prefill, decode = [], []
        for rid in list(self.open):
            tr = self.open[rid]
            st = states[rid]
            n_before, n_after = len(tr.times), len(st["tokens"])
            plen = len(tr.req.prompt)
            for n in range(n_before, n_after):
                if n == 0:
                    prefill.append(plen)
                else:           # the token after n cached ones
                    decode.append(plen + n)
                tr.times.append(t1)
            if st["state"] in TERMINAL:
                tr.state = st["state"]
                del self.open[rid]
        rec = {"t0": t0, "t1": t1, "prefill": prefill, "decode": decode}
        self.steps.append(rec)
        return rec


def sample(tracked: list, k: int, seed: int) -> list:
    """``k`` finished requests drawn from the seed, the longest answer
    among them."""
    done = [t for t in tracked if t.state == "DONE"]
    if not done:
        return []
    longest = max(done, key=lambda t: (len(t.times), len(t.req.prompt)))
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng([int(seed) % 2 ** 63, 1])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(run, params, served: list, *, fp8: bool = False,
                qblock: int = 512) -> dict:
    """Check served greedy tokens against the float32 reference.

    ``served`` holds ``(prompt, tokens)``.  At every served position the
    reference gives logits z over the prompt and the tokens served before;
    a token's gap is ``(max z - z[token]) / max |z|``: 0 when it is the
    reference's own choice, small for a near tie, about 1 for a wrong
    one.  With ``fp8`` the tokens are instead those the fp8 control ranks
    first at the same positions (the control need not decode).
    Returns the widest gap, the count of tokens, and of those that are
    not the reference's argmax."""
    import jax.numpy as jnp

    ref = run.reference
    pad = run.workload["engine"]["max_len"]
    qblock = min(qblock, pad)
    pad = -(-pad // qblock) * qblock
    worst, n, flips = 0.0, 0, 0
    for prompt, toks in served:
        seq = np.zeros(pad, np.int32)
        ctx = list(prompt) + list(toks[:-1])
        seq[:len(ctx)] = ctx
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
        z = np.asarray(ref.logits_at(params, run.config, seq, rows,
                                     qblock=qblock), np.float64)
        if fp8:
            z8 = ref.logits_at(params, run.config, seq, rows, fp8=True,
                               qblock=qblock)
            toks = np.asarray(jnp.argmax(z8, -1))
        top = z.max(-1)
        got = z[np.arange(len(toks)), np.asarray(toks)]
        gaps = (top - got) / np.abs(z).max(-1)
        worst = max(worst, float(gaps.max()))
        n += len(toks)
        flips += int((np.asarray(toks) != z.argmax(-1)).sum())
    if n == 0:          # nothing was served to compare: as a wrong token
        worst = 1.0
    return {"worst_gap": worst, "tokens": n, "not_argmax": flips}


def spans(sink_events: list) -> list:
    """Program spans from a tracer's ``(kind, fields)`` events: each as
    ``{"name", "start", "end", "attrs"}`` (begin and end attributes)."""
    open_, out = {}, []
    for kind, f in sink_events:
        if kind == "span_begin":
            open_[f["sid"]] = f
        elif kind == "span_end" and f["sid"] in open_:
            b = open_.pop(f["sid"])
            attrs = {k: v for k, v in b.items()
                     if k not in ("sid", "ts", "name")}
            attrs.update({k: v for k, v in f.items()
                          if k not in ("sid", "ts")})
            out.append({"name": b["name"], "start": b["ts"],
                        "end": f["ts"], "attrs": attrs})
    return out


class ListSink:
    """An in-memory event sink for the program's tracer."""

    def __init__(self):
        self.events: list = []

    def emit(self, kind: str, **fields) -> None:
        self.events.append((kind, fields))


def attach_tracer(engine) -> ListSink:
    from repro.obs.trace import Tracer
    sink = ListSink()
    engine.tracer = Tracer(sink, pid="engine")
    return sink

