"""Continuous-batching serve engine over the prefill/decode steps.

One engine step = deadline shedding + (bounded) admissions + one decode
round:

* admission: FCFS requests claim a pool slot, prefill at a static prompt
  BUCKET (padded; the bucket's suffix positions never contaminate the
  prefix under causal attention, so cache rows and the last-valid logit
  are token-exact vs an unpadded prefill), get scattered into the slot
  with one fused update, and sample their first token (TTFT);
* decode: ONE jitted step over the whole pool — every shape is static at
  ``(max_slots, max_len)``, occupancy lives purely in the per-slot
  ``pos`` lengths and the active mask, and the split-K decode kernel's
  length-aware early-outs make the padded tail of every slot cost ~no
  compute.  Joining and retiring requests therefore NEVER re-jits: after
  ``warmup()`` the program cache is frozen (asserted in tests via the
  jit cache counters).

Retirement (EOS or max-new-tokens) frees the slot back to the pool; the
row's stale bytes are simply never read again and are fully overwritten
by the next scatter.

Fault tolerance (ISSUE 7) — detect, degrade, recover:

* a health sentinel is FUSED into the jitted decode program: per slot,
  all-finite logits AND sampled-token-in-vocab AND a scattered prompt
  (``pos > 0``).  The verdict rides IN the fetched token value (a
  tripped slot yields -1; no vocab id is negative), so the steady-state
  path fetches the same single ``(max_slots,)`` int32 it always did —
  no extra host sync, no recompile (asserted via ``compile_counts``);
* a tripped sentinel quarantines the poisoned slot
  (``SlotPool.quarantine``), audits the pool's alloc/free invariant
  (``SlotPool.audit``), and releases the slot only after the audit
  passes — the next scatter fully overwrites the row;
* the victim request replays deterministically from its prompt plus the
  already-emitted (healthy) tokens: it re-enters the queue at the HEAD
  with a retry backoff, re-prefills over the extended prompt, and keeps
  generating.  A bounded per-request retry budget (``max_retries``)
  escalates persistent faults to ``FAILED``;
* per-request deadlines (queue TTL) shed stale queued requests to
  ``DROPPED``; a bounded queue rejects submits (``AdmissionRejected``);
  ``cancel`` and ``drain`` give callers explicit control; ``run`` on a
  stuck trace returns a partial summary flagged ``stalled`` instead of
  discarding every metric in a raise.

``hooks`` is the seam the fault-injection harness (``serve/faults.py``)
uses: optional host-side callables consulted around the jit boundaries
("pre_step", "pre_decode", "scatter_filter") — they never touch compiled
programs, so injection cannot recompile anything.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mixed_precision import get_policy
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serve import sampling
from repro.serve.cache_pool import SlotPool, scatter_request
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import (CANCELLED, DECODE, FAILED, MIGRATED,
                                   QUEUED, TERMINAL, AdmissionRejected,
                                   Request, Scheduler)
from repro.serve.trace import TraceRequest


def default_buckets(max_len: int, lo: int = 16) -> tuple[int, ...]:
    """Power-of-two prompt buckets up to max_len (one compile each)."""
    out = []
    b = lo
    while b < max_len:
        out.append(b)
        b *= 2
    return tuple(out) or (max_len,)


def supports(cfg: ModelConfig) -> bool:
    """Engine eligibility: the slot-pooled per-row decode path needs the
    GQA kvq cache layout and a uniform window schedule."""
    return (cfg.mixer == "attn" and cfg.mla is None
            and cfg.encoder is None and not cfg.global_layers)


class ServeEngine:
    """Slot-pooled continuous-batching engine (see module docstring)."""

    def __init__(self, params, cfg: ModelConfig, *, max_slots: int,
                 max_len: int, prompt_buckets: Sequence[int] | None = None,
                 policy_name: str = "bf16", quantized: bool = True,
                 kv_backend: str = "auto", kv_splits: int = 1,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 eos_id: Optional[int] = None,
                 max_prefill_per_step: int = 1,
                 mem_budget_bytes: Optional[int] = None, mesh=None,
                 max_queue: Optional[int] = None,
                 deadline_steps: Optional[int] = None,
                 max_retries: int = 2, retry_backoff_steps: int = 1,
                 sampler_keys: str = "step", sink=None):
        if not supports(cfg):
            raise NotImplementedError(
                "ServeEngine needs a GQA attention arch with a uniform "
                "window schedule (no MLA latents, SSM state, encoder "
                "cross-attention, or per-layer global overrides) — those "
                "serve through the lockstep driver")
        if max_retries < 0 or retry_backoff_steps < 0:
            raise ValueError("ServeEngine: max_retries and "
                             "retry_backoff_steps must be >= 0")
        if sampler_keys not in ("step", "request"):
            raise ValueError(f"ServeEngine: sampler_keys must be 'step' or "
                             f"'request', got {sampler_keys!r}")
        # "step": one key per decode round, folded on a global draw
        # counter (the PR 5 behavior — deterministic for a fixed engine
        # but placement-dependent).  "request": every row samples with
        # fold_in(fold_in(base, key_id), draw) — token `draw` of request
        # `key_id` gets the same key on any replica/slot/step, which is
        # what makes fleet migration trajectory-preserving under
        # sampling (the router's mode).
        self.sampler_keys = sampler_keys
        if kv_backend == "auto":          # compiled kvq kernel on a TPU
            from repro.kernels import platform_backend
            kv_backend = platform_backend()
        #: the int8 decode-attention backend that runs (``auto`` resolved)
        self.kv_backend = kv_backend
        self.cfg = cfg
        self.mesh = mesh
        self.max_len = max_len
        self.quantized = quantized
        self.eos_id = eos_id
        self.deadline_steps = deadline_steps
        self.max_retries = max_retries
        self.retry_backoff_steps = retry_backoff_steps
        self.temperature, self.top_k = float(temperature), int(top_k)
        #: host-side interception points around the jit boundaries (the
        #: fault-injection seam; see module docstring) — never compiled
        self.hooks: dict[str, Callable] = {}
        self._tracer = None               # repro.obs.Tracer via .tracer
        self.capacity_report = None
        if mem_budget_bytes is not None:
            from repro import plan as plan_mod
            # with a mesh the budget means bytes PER CHIP — the same
            # contract the training planner applies to --mem-budget-mb
            self.capacity_report = plan_mod.serve_capacity_report(
                cfg, max_len, mem_budget_bytes, quantized=quantized,
                mesh=mesh)
            cap = self.capacity_report["max_slots"]
            if cap < 1:
                raise ValueError(
                    f"ServeEngine: memory budget {mem_budget_bytes} admits "
                    f"0 slots at max_len={max_len} "
                    f"({self.capacity_report['bytes_per_slot_per_device']} "
                    f"B/slot/device)")
            max_slots = min(max_slots, cap)
        self.pool = SlotPool(cfg, max_slots, max_len, quantized=quantized,
                             mesh=mesh)
        if mesh is not None:
            from repro.distributed import sharding as shd
            p_specs = shd.param_specs(cfg, params, mesh=mesh)
            self._p_shard = shd.to_shardings(mesh, p_specs)
            params = jax.device_put(params, self._p_shard)
        self.params = params
        self.scheduler = Scheduler(
            max_slots, bytes_per_slot=self.pool.bytes_per_slot_per_device(),
            byte_budget=mem_budget_bytes,
            max_prefill_per_step=max_prefill_per_step, max_queue=max_queue)
        self.metrics = ServeMetrics(sink=sink)
        self.buckets = tuple(sorted(prompt_buckets
                                    if prompt_buckets is not None
                                    else default_buckets(max_len)))
        if self.buckets[-1] > max_len:
            raise ValueError(f"prompt bucket {self.buckets[-1]} exceeds "
                             f"max_len {max_len}")

        policy = get_policy(policy_name)
        self._key = jax.random.PRNGKey(seed)
        per_req = sampler_keys == "request"

        def _decode_logits(params, cache, tokens, active):
            # sampling is FUSED into the decode program: one dispatch per
            # engine step, and the token/active buffers never round-trip
            # through the host on the steady-state path
            pos_before = cache["pos"]
            logits, cache = transformer.decode_step(
                params, cfg, cache, tokens, policy=policy,
                quantized=quantized, kvq_backend=kv_backend,
                kvq_splits=kv_splits, active=active, mesh=mesh)
            return pos_before, logits, cache

        def _verdict(pos_before, logits, sampled, active, tokens):
            # health sentinel, fused into the same program: a live slot is
            # healthy iff its logits are all finite (the padded-vocab mask
            # is a finite -1e30 by design), its sampled token is a real
            # vocab id, and a prompt was actually scattered into the row
            # (pos > 0 pre-increment — a dropped scatter leaves 0).  The
            # verdict rides IN the token value: a tripped slot yields -1
            # (no vocab id is negative), so the steady-state path still
            # fetches exactly one (max_slots,) int32 — no second device
            # array, no extra host sync, no recompile.  A faulted slot's
            # -1 never feeds a real decode: the engine deactivates the
            # slot before its next step and re-joins it with a fresh
            # token.
            healthy = (jnp.isfinite(logits).all(axis=-1)
                       & (sampled >= 0) & (sampled < cfg.vocab)
                       & (pos_before > 0))
            return jnp.where(active & healthy, sampled,
                             jnp.where(active, jnp.int32(-1), tokens))

        def _decode(params, cache, tokens, active, key):
            pos_before, logits, cache = _decode_logits(params, cache,
                                                       tokens, active)
            sampled = sampling.sample_tokens(
                logits, key, temperature=self.temperature, top_k=self.top_k)
            return _verdict(pos_before, logits, sampled, active,
                            tokens), cache

        base_key = self._key

        def _decode_req(params, cache, tokens, active, kids, draws):
            # "request" key mode: each row folds its OWN key from the
            # request identity and per-request draw counter, both living
            # on device — the draw counter increments inside the same
            # program, so per-request keys add no host traffic
            pos_before, logits, cache = _decode_logits(params, cache,
                                                       tokens, active)
            keys = jax.vmap(sampling.fold_request_key,
                            in_axes=(None, 0, 0))(base_key, kids, draws)
            sampled = sampling.sample_tokens_per_row(
                logits, keys, temperature=self.temperature,
                top_k=self.top_k)
            new_draws = jnp.where(active, draws + 1, draws)
            return _verdict(pos_before, logits, sampled, active,
                            tokens), cache, new_draws

        def _prefill(bucket, params, tokens, true_len):
            # mesh: _kv_entry pins each cache entry's sharding as it is
            # built, so the prefill scan carries the pool's layout from the
            # start instead of XLA re-sharding the finished cache
            logits, aux = transformer.forward(
                params, cfg, {"tokens": tokens}, policy=policy,
                build_cache=True, cache_quantized=quantized, mesh=mesh)
            # last VALID position, not bucket-1: padded suffix logits are
            # garbage by contract
            last = jax.lax.dynamic_index_in_dim(logits, true_len - 1, axis=1,
                                                keepdims=False)
            cache = transformer.grow_cache(aux["cache"], self.max_len)
            return last, cache

        def _join(tokens, active, slot, tok):
            return tokens.at[slot].set(tok), active.at[slot].set(True)

        def _join_req(tokens, active, kids, draws, slot, tok, kid, draw0):
            # request-key mode also stamps the row's sampler identity and
            # its next draw index (len(emitted) + 1 at join time)
            return (tokens.at[slot].set(tok), active.at[slot].set(True),
                    kids.at[slot].set(kid), draws.at[slot].set(draw0))

        def _leave(active, slot):
            return active.at[slot].set(False)

        # donate cache + tokens (both returned); active is reused across
        # steps and must NOT be donated
        self._rep = None
        if mesh is None:
            if per_req:
                self._decode_fn = jax.jit(_decode_req,
                                          donate_argnums=(1, 2, 5))
                self._join_fn = jax.jit(_join_req,
                                        donate_argnums=(0, 1, 2, 3))
            else:
                self._decode_fn = jax.jit(_decode, donate_argnums=(1, 2))
                self._join_fn = jax.jit(_join, donate_argnums=(0, 1))
            self._scatter_fn = jax.jit(scatter_request, donate_argnums=(0,))
            self._prefill_fns = {
                b: jax.jit(functools.partial(_prefill, b))
                for b in self.buckets}
            self._leave_fn = jax.jit(_leave, donate_argnums=(0,))
        else:
            # every program pins its shardings explicitly, so the cache's
            # placement is an INPUT contract, not an XLA choice — decode
            # and scatter are sharding-preserving end to end and nothing
            # on the steady-state path can re-gather the pool (asserted
            # against the compiled HLO via decode_hlo() in tests)
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.distributed import sharding as shd
            rep = NamedSharding(mesh, P())
            c_shard = shd.to_shardings(mesh, self.pool.specs)
            req_sds = jax.eval_shape(
                lambda: transformer.init_cache(cfg, 1, max_len,
                                               quantized=quantized))
            req_shard = shd.to_shardings(
                mesh, shd.serve_cache_specs(cfg, req_sds, mesh))
            if per_req:
                self._decode_fn = jax.jit(
                    _decode_req, donate_argnums=(1, 2, 5),
                    in_shardings=(self._p_shard, c_shard, rep, rep, rep,
                                  rep),
                    out_shardings=(rep, c_shard, rep))
            else:
                self._decode_fn = jax.jit(
                    _decode, donate_argnums=(1, 2),
                    in_shardings=(self._p_shard, c_shard, rep, rep, rep),
                    out_shardings=(rep, c_shard))
            self._scatter_fn = jax.jit(
                scatter_request, donate_argnums=(0,),
                in_shardings=(c_shard, req_shard, rep, rep),
                out_shardings=c_shard)
            self._prefill_fns = {
                b: jax.jit(functools.partial(_prefill, b),
                           in_shardings=(self._p_shard, rep, rep),
                           out_shardings=(rep, req_shard))
                for b in self.buckets}
            # join/leave must pin shardings too: an unspecified jit would
            # commit tokens/active to one device, and every downstream
            # program keyed on the committed layout would recompile
            if per_req:
                self._join_fn = jax.jit(
                    _join_req, donate_argnums=(0, 1, 2, 3),
                    in_shardings=(rep,) * 8,
                    out_shardings=(rep, rep, rep, rep))
            else:
                self._join_fn = jax.jit(
                    _join, donate_argnums=(0, 1),
                    in_shardings=(rep, rep, rep, rep),
                    out_shardings=(rep, rep))
            self._leave_fn = jax.jit(
                _leave, donate_argnums=(0,),
                in_shardings=(rep, rep), out_shardings=rep)
            self._rep = rep
        self._sampler = sampling.make_sampler(temperature=self.temperature,
                                              top_k=self.top_k)

        self._draws = 0
        self._step_no = 0
        self._next_rid = 0
        self._draining = False
        self._slot_req: dict[int, Request] = {}
        self._requests: dict[int, Request] = {}            # every rid ever
        self._requests_done: list[Request] = []
        self._tokens_dev = self._replicated(jnp.zeros((max_slots,), jnp.int32))
        self._active_dev = self._replicated(jnp.zeros((max_slots,), bool))
        self._kids_dev = self._replicated(jnp.zeros((max_slots,), jnp.int32))
        self._draws_dev = self._replicated(jnp.zeros((max_slots,), jnp.int32))
        self._active_buf = np.zeros((max_slots,), bool)    # host mirror

    # -- public API --------------------------------------------------------
    @property
    def step_no(self) -> int:
        return self._step_no

    @property
    def tracer(self):
        """repro.obs Tracer, or None (tracing off — the default).  All
        span emission is host-side and guarded on this being set, so the
        untraced path pays nothing and nothing traced runs inside jit.
        Attach AFTER ``warmup()`` (the warmup probe would otherwise leave
        a phantom rid-0 trace)."""
        return self._tracer

    @tracer.setter
    def tracer(self, t) -> None:
        self._tracer = t
        self.scheduler.tracer = t         # queue-wait spans live there

    def _end_req_span(self, req: Request, state: str) -> None:
        """Close a request's open decode + root spans at terminal time."""
        if self._tracer is not None:
            self._tracer.end(req.span_ids.pop("decode", None), state=state)
            self._tracer.end(req.span_ids.pop("req", None), state=state,
                             tokens=len(req.tokens))

    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None,
               arrival_step: Optional[int] = None,
               deadline_steps: Optional[int] = None,
               front: bool = False, key_id: Optional[int] = None,
               emitted: Optional[Sequence[int]] = None) -> int:
        """Queue a request; returns its rid.  FCFS from here on.

        Raises :class:`AdmissionRejected` when the bounded queue is full
        (backpressure — the request never entered the system).
        ``deadline_steps`` is a queue TTL in engine steps (None falls
        back to the engine default): a request still queued past it is
        shed to ``DROPPED`` instead of waiting forever.  ``front`` joins
        at the queue HEAD (the router's migration path); ``key_id``
        overrides the sampler-key identity in ``sampler_keys="request"``
        mode (the router passes the fleet-global rid); ``emitted`` seeds
        the already-generated healthy tokens of a request migrating IN
        from another replica — admission then rides the engine's own
        replay path (prefill over prompt+emitted, first new draw index
        = len(emitted)), so the continuation is token-exact under greedy
        and key-exact in "request" mode."""
        prompt = np.asarray(prompt, np.int32)
        req = Request(rid=self._next_rid, prompt=prompt,
                      max_new_tokens=max_new_tokens,
                      arrival_step=(self._step_no if arrival_step is None
                                    else arrival_step),
                      eos_id=eos_id if eos_id is not None else self.eos_id,
                      deadline_steps=(deadline_steps
                                      if deadline_steps is not None
                                      else self.deadline_steps),
                      key_id=key_id)
        if emitted:
            if len(emitted) >= max_new_tokens:
                raise ValueError(f"request {req.rid}: emitted prefix "
                                 f"{len(emitted)} leaves no tokens to "
                                 f"generate (max_new_tokens "
                                 f"{max_new_tokens})")
            req.tokens = [int(t) for t in emitted]
        if req.prompt_len + len(req.tokens) > self.buckets[-1]:
            raise ValueError(f"request {req.rid}: prompt_len "
                             f"{req.prompt_len}+{len(req.tokens)} emitted "
                             f"exceeds largest bucket {self.buckets[-1]}")
        if req.total_len() > self.max_len:
            raise ValueError(f"request {req.rid}: prompt+gen "
                             f"{req.total_len()} exceeds max_len "
                             f"{self.max_len}")
        if self._tracer is not None:
            req.span_ids["req"] = self._tracer.begin(
                "req", trace=self._kid(req), rid=req.rid,
                prompt_len=req.prompt_len, max_new_tokens=max_new_tokens,
                replay=bool(emitted))
        try:
            self.scheduler.submit(req, front=front)
        except AdmissionRejected:
            self.metrics.on_reject()
            if self._tracer is not None:
                self._tracer.end(req.span_ids.pop("req", None),
                                 state="REJECTED", tokens=0)
            raise
        self._next_rid += 1
        self._requests[req.rid] = req
        self.metrics.on_submit(req.rid, self._step_no)
        return req.rid

    def evict_request(self, rid: int,
                      state: str = MIGRATED) -> Optional[Request]:
        """Remove a queued or resident request into a terminal state and
        return it (None if unknown or already terminal).  The router's
        migration path: the returned request's ``tokens`` are the
        healthy emitted prefix, which — prepended to the prompt — is the
        deterministic replay input on another replica.  A resident
        request's slot goes straight back to the pool (its cache bytes
        are dead by contract; the next scatter overwrites them)."""
        req = self._requests.get(rid)
        if req is None or req.state in TERMINAL:
            return None
        if req.state == QUEUED:
            self.scheduler.remove_queued(req, state)
        else:
            self.scheduler.retire(req, state=state)
            self._evict(req)
        self.metrics.on_terminal(rid, state)
        self._end_req_span(req, state)
        return req

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or resident request.  Returns True if it was
        cancelled, False if unknown or already terminal (idempotent —
        cancelling a request that retired in the same step is a safe
        no-op)."""
        return self.evict_request(rid, CANCELLED) is not None

    def drain(self, *, cancel_queued: bool = True,
              max_steps: Optional[int] = None) -> dict:
        """Graceful shutdown: admit nothing new, let resident requests
        finish, and return the final summary.  Queued requests are
        cancelled by default (with ``cancel_queued=False`` they stay
        queued for a later ``run``/``step``)."""
        if cancel_queued:
            for req in list(self._requests.values()):
                if req.state == QUEUED:
                    self.cancel(req.rid)
        self._draining = True
        try:
            budget = max_steps if max_steps is not None else \
                8 * (self.max_len + 1) * max(1, self.scheduler.resident)
            while self.scheduler.resident > 0:
                self.step()
                budget -= 1
                if budget < 0:
                    return self.summary(stalled=True)
        finally:
            self._draining = False
        if cancel_queued:
            # a fault mid-drain can requeue a replay; it can't be admitted
            # while draining, so cancel it rather than strand it
            for req in list(self._requests.values()):
                if req.state == QUEUED:
                    self.cancel(req.rid)
        return self.summary()

    def decode_hlo(self) -> str:
        """Compiled-HLO text of the decode round, at the live buffers'
        exact shapes/shardings — what tests grep to assert the KV cache
        is never all-gathered after warmup."""
        if self.sampler_keys == "request":
            return self._decode_fn.lower(
                self.params, self.pool.cache, self._tokens_dev,
                self._active_dev, self._kids_dev,
                self._draws_dev).compile().as_text()
        return self._decode_fn.lower(
            self.params, self.pool.cache, self._tokens_dev,
            self._active_dev, self._key).compile().as_text()

    def compile_counts(self) -> dict:
        """jit program-cache sizes — the zero-recompile contract's meter."""
        counts = {"decode": self._decode_fn._cache_size(),
                  "scatter": self._scatter_fn._cache_size(),
                  "join": self._join_fn._cache_size(),
                  "leave": self._leave_fn._cache_size(),
                  "sampler": self._sampler._cache_size()}
        for b, fn in self._prefill_fns.items():
            counts[f"prefill_{b}"] = fn._cache_size()
        return counts

    def warmup(self) -> dict:
        """Compile every program the engine can ever need, then reset all
        request state.  After this, joins/retirements are recompile-free
        (``compile_counts`` is frozen; tests assert it)."""
        for b, fn in self._prefill_fns.items():
            # compile each prompt-bucket program directly: the admission
            # path can't exercise a bucket b == max_len (prompt b plus one
            # generated token would exceed max_len), and a shorter probe
            # prompt could fall into an adjacent bucket instead
            jax.block_until_ready(
                fn(self.params, jnp.zeros((1, b), jnp.int32), jnp.int32(b)))
        if self.max_len >= 3:
            # one real request drives admission + one decode round, which
            # compiles decode/scatter/join/leave/sampler; eos_id=-1 (no
            # vocab token is negative) so an engine-level eos_id can't
            # retire the zeros probe at admission before decode compiles
            plen = min(self.buckets[0], self.max_len - 2)
            self.submit(np.zeros((plen,), np.int32), 2, eos_id=-1)
            guard = 8 * (self.max_len + len(self.buckets))
            for _ in range(guard):
                if not self.scheduler.has_work():
                    break
                self.step()
        assert not self.scheduler.has_work(), "warmup trace did not drain"
        self.reset()
        return self.compile_counts()

    def reset(self) -> None:
        """Drop all request state; keep the compiled programs."""
        assert self.scheduler.resident == 0 and not self.scheduler.has_work(), \
            "reset with in-flight requests"
        self.pool = SlotPool(self.cfg, self.pool.max_slots, self.max_len,
                             quantized=self.quantized, mesh=self.mesh)
        self.scheduler = Scheduler(
            self.pool.max_slots,
            bytes_per_slot=self.pool.bytes_per_slot_per_device(),
            byte_budget=self.scheduler.byte_budget,
            max_prefill_per_step=self.scheduler.max_prefill_per_step,
            max_queue=self.scheduler.max_queue)
        self.scheduler.tracer = self._tracer
        self.metrics = ServeMetrics(sink=self.metrics.sink,
                                    replica=self.metrics.replica)
        self._draws = 0
        self._step_no = 0
        self._next_rid = 0
        self._draining = False
        self._slot_req.clear()
        self._requests.clear()
        self._requests_done.clear()
        self._tokens_dev = self._replicated(
            jnp.zeros((self.pool.max_slots,), jnp.int32))
        self._active_dev = self._replicated(
            jnp.zeros((self.pool.max_slots,), bool))
        self._kids_dev = self._replicated(
            jnp.zeros((self.pool.max_slots,), jnp.int32))
        self._draws_dev = self._replicated(
            jnp.zeros((self.pool.max_slots,), jnp.int32))
        self._active_buf[:] = False

    # -- engine internals --------------------------------------------------
    def _replicated(self, x):
        """Commit a host-built buffer to the mesh (replicated) so every
        program sees one consistent placement; no-op without a mesh."""
        return x if self._rep is None else jax.device_put(x, self._rep)

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt_len {n} exceeds largest bucket")

    def _next_key(self):
        if self.temperature <= 0.0:
            return self._key              # greedy never consumes the key
        k = jax.random.fold_in(self._key, self._draws)
        self._draws += 1
        return k

    def _kid(self, req: Request) -> int:
        """The request's sampler-key identity ("request" mode): the
        fleet-global id if the router set one, else the local rid."""
        return req.key_id if req.key_id is not None else req.rid

    def _first_key(self, req: Request):
        """PRNG key for a request's FIRST token after (re-)prefill.  In
        "request" mode it folds on the request identity and the emitted
        count — so a replay's first new token draws the same key it
        would have drawn on the original placement."""
        if self.sampler_keys != "request":
            return self._next_key()
        if self.temperature <= 0.0:
            return self._key              # greedy never consumes the key
        return sampling.fold_request_key(self._key, self._kid(req),
                                         len(req.tokens))

    def _evict(self, req: Request) -> None:
        """Release a resident request's slot + device state (terminal
        transitions and replays share this; the scheduler transition
        happens at the caller)."""
        self.pool.free(req.slot)
        self._active_buf[req.slot] = False
        self._active_dev = self._leave_fn(self._active_dev,
                                          jnp.int32(req.slot))
        del self._slot_req[req.slot]
        req.slot = None

    def _emit(self, req: Request, tok: int) -> None:
        """Record one sampled token; retire the request when finished."""
        req.tokens.append(tok)
        self.metrics.on_token(req.rid, self._step_no)
        hit_eos = req.eos_id is not None and tok == req.eos_id
        if hit_eos or len(req.tokens) >= req.max_new_tokens:
            self.scheduler.retire(req)
            self.metrics.on_done(req.rid)
            self._evict(req)
            self._requests_done.append(req)
            self._end_req_span(req, req.state)

    def _replay_prompt(self, req: Request) -> np.ndarray:
        """Prompt + already-emitted (healthy) tokens: the deterministic
        replay input.  Under greedy decode the continuation is
        token-exact; under sampling it is seeded-deterministic (same
        seed + same fault schedule -> same tokens)."""
        if not req.tokens:
            return req.prompt
        return np.concatenate([req.prompt,
                               np.asarray(req.tokens, np.int32)])

    def _fault(self, req: Request) -> None:
        """The decode sentinel tripped on ``req``'s slot: quarantine the
        poisoned row, audit the pool, then replay or fail the victim.

        The faulted step's sampled token is NEVER emitted — the client
        only ever sees healthy tokens, which is what makes the replay
        prefix exact."""
        slot = req.slot
        self.metrics.on_fault(req.rid)
        if self._tracer is not None:
            self._tracer.end(req.span_ids.pop("decode", None), state="FAULT",
                             fault=True)
        self.pool.quarantine(slot)
        self._active_buf[slot] = False
        self._active_dev = self._leave_fn(self._active_dev, jnp.int32(slot))
        del self._slot_req[slot]
        req.slot = None
        self.pool.audit()                 # alloc/free invariant still holds?
        self.pool.release_quarantined()   # row is dead; next scatter overwrites

        reason = None
        if req.retries >= self.max_retries:
            reason = (f"retry budget exhausted "
                      f"({req.retries}/{self.max_retries})")
        elif len(self._replay_prompt(req)) > self.buckets[-1]:
            reason = (f"replay prompt {len(self._replay_prompt(req))} "
                      f"exceeds largest bucket {self.buckets[-1]}")
        if reason is not None:
            self.scheduler.retire(req, state=FAILED)
            req.fail_reason = reason
            self.metrics.on_terminal(req.rid, FAILED)
            self._end_req_span(req, FAILED)
            return
        req.retries += 1
        # backoff: the replay waits retries * backoff steps at the head
        # of the line before re-prefilling
        self.scheduler.requeue(
            req, self._step_no + 1 + self.retry_backoff_steps * req.retries)
        self.metrics.on_retry(req.rid)

    def step(self) -> None:
        """Deadline shedding + admissions (bounded prefills) + one decode
        round with the fused health sentinel."""
        hook = self.hooks.get("pre_step")
        if hook is not None:
            hook(self)
        step_sid = None if self._tracer is None else \
            self._tracer.begin("step", step=self._step_no)
        for req in self.scheduler.shed_expired(self._step_no):
            self.metrics.on_terminal(req.rid, req.state)
            self._end_req_span(req, req.state)

        admitted = [] if self._draining else \
            self.scheduler.pop_admissible(self.pool.free_slots, self._step_no)
        scatter_ok = self.hooks.get("scatter_filter")
        for req in admitted:
            if self._tracer is not None:
                req.span_ids["prefill"] = self._tracer.begin(
                    "prefill", trace=self._kid(req),
                    parent=req.span_ids.get("req"))
            slot = self.pool.alloc()
            assert slot is not None       # pop_admissible checked free_slots
            prompt = self._replay_prompt(req)   # == req.prompt first time
            plen = len(prompt)
            b = self._bucket_for(plen)
            padded = np.zeros((1, b), np.int32)
            padded[0, :plen] = prompt
            logits, req_cache = self._prefill_fns[b](
                self.params, jnp.asarray(padded), jnp.int32(plen))
            if scatter_ok is None or scatter_ok(self, req, slot):
                self.pool.cache = self._scatter_fn(
                    self.pool.cache, req_cache, jnp.int32(slot),
                    jnp.int32(plen))
            tok = int(np.asarray(self._sampler(logits, self._first_key(req)))[0])
            req.state = DECODE
            req.slot = slot
            self._slot_req[slot] = req
            if self.sampler_keys == "request":
                # stamp identity + next draw index (the first token drew
                # at index len(tokens); join runs before _emit appends it)
                (self._tokens_dev, self._active_dev, self._kids_dev,
                 self._draws_dev) = self._join_fn(
                    self._tokens_dev, self._active_dev, self._kids_dev,
                    self._draws_dev, jnp.int32(slot), jnp.int32(tok),
                    jnp.int32(self._kid(req)),
                    jnp.int32(len(req.tokens) + 1))
            else:
                self._tokens_dev, self._active_dev = self._join_fn(
                    self._tokens_dev, self._active_dev, jnp.int32(slot),
                    jnp.int32(tok))
            self._active_buf[slot] = True
            if self._tracer is not None:
                # prefill closes at the first sampled token (the TTFT
                # edge); decode residency is its own span from here
                self._tracer.end(req.span_ids.pop("prefill", None),
                                 bucket=b, plen=plen, slot=int(slot))
            self._emit(req, tok)          # first token: the TTFT sample
            if self._tracer is not None and req.state == DECODE:
                req.span_ids["decode"] = self._tracer.begin(
                    "decode", trace=self._kid(req),
                    parent=req.span_ids.get("req"), slot=int(slot))

        if self._active_buf.any():
            hook = self.hooks.get("pre_decode")
            if hook is not None:
                hook(self)
            live = np.nonzero(self._active_buf)[0]      # snapshot pre-emit
            if self.sampler_keys == "request":
                (self._tokens_dev, self.pool.cache,
                 self._draws_dev) = self._decode_fn(
                    self.params, self.pool.cache, self._tokens_dev,
                    self._active_dev, self._kids_dev, self._draws_dev)
            else:
                self._tokens_dev, self.pool.cache = self._decode_fn(
                    self.params, self.pool.cache, self._tokens_dev,
                    self._active_dev, self._next_key())
            # one host sync, same as the fault-free path: the sentinel
            # verdict is encoded in the token sign (-1 = tripped)
            toks = np.asarray(self._tokens_dev)
            for slot in live:
                req = self._slot_req[int(slot)]
                if toks[slot] >= 0:
                    self._emit(req, int(toks[slot]))
                else:
                    self._fault(req)

        self.metrics.on_step(self._step_no, self.scheduler.queue_depth,
                             self.pool.occupancy)
        if self._tracer is not None:
            self._tracer.end(step_sid, admitted=len(admitted),
                             occupancy=self.pool.occupancy)
        self._step_no += 1

    def request_states(self) -> dict:
        """Light host-side view of every request: ``rid -> {state,
        tokens, slot}``.  The subprocess worker's harvest payload (the
        router's ``_harvest`` reads the same fields off in-process
        engines directly), and the WAL's token-delta source."""
        return {rid: {"state": r.state, "tokens": list(r.tokens),
                      "slot": r.slot}
                for rid, r in self._requests.items()}

    def summary(self, *, stalled: bool = False) -> dict:
        """Metrics summary + live scheduler/pool diagnostics.  Always
        complete — a stalled run flags ``stalled=True`` instead of
        throwing the metrics away."""
        out = self.metrics.summary(max_slots=self.pool.max_slots)
        out["stalled"] = stalled
        out["diagnostics"] = {
            "step_no": self._step_no,
            "queue_depth": self.scheduler.queue_depth,
            "resident": self.scheduler.resident,
            "state_counts": self.scheduler.state_counts(),
            "pool": {"occupancy": self.pool.occupancy,
                     "free": self.pool.free_slots,
                     "quarantined": self.pool.quarantined,
                     "allocs": self.pool.allocs, "frees": self.pool.frees,
                     "quarantines": self.pool.quarantines},
        }
        return out

    def run(self, trace: Sequence[TraceRequest], *,
            max_steps: Optional[int] = None) -> dict:
        """Drive a trace to completion; returns the metrics summary.

        Arrivals are step-indexed: a request is submitted once the engine
        reaches its ``arrival_step``; idle gaps (empty pool, nothing
        arrived) fast-forward instead of burning decode rounds.  Trace
        submits hitting a full bounded queue are REJECTED (counted in
        the summary), and a run that exceeds its step budget returns a
        partial summary flagged ``stalled`` with scheduler/pool
        diagnostics instead of raising away every metric.
        """
        pending = sorted(trace, key=lambda r: r.arrival_step)
        i = 0
        budget = max_steps if max_steps is not None else (
            sum((r.max_new_tokens + 2) * (self.max_retries + 1)
                for r in pending)
            + (pending[-1].arrival_step if pending else 0) + 16)
        while i < len(pending) or self.scheduler.has_work():
            while (i < len(pending)
                   and pending[i].arrival_step <= self._step_no):
                r = pending[i]
                try:
                    self.submit(r.prompt, r.max_new_tokens)
                except AdmissionRejected:
                    pass                  # backpressure: counted, shed
                i += 1
            if not self.scheduler.has_work() and i < len(pending):
                self._step_no = pending[i].arrival_step   # fast-forward idle
                continue
            self.step()
            budget -= 1
            if budget < 0:
                return self.summary(stalled=True)
        return self.summary()
