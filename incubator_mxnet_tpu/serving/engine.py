"""Production inference serving engine: continuous batching over a
paged KV cache (docs/serving.md).

``TransformerLM.generate`` decodes one fixed-shape batch per call —
fine for a notebook, fatal at traffic: a mixed stream pays worst-case
padding, head-of-line blocking, and a dense max-length KV buffer per
sequence.  :class:`ServingEngine` replaces that with:

- **Paged KV cache** — per-layer block pools
  (``block_table.BlockPool``); each request owns just the blocks its
  actual length needs, gather/scatter happens by block id INSIDE the
  jitted step, and refcounting makes shared system prompts copy-free
  (``cache_manager.PrefixCache``).
- **Continuous batching** — ``submit()`` enqueues, every ``step()``
  admits waiting requests into free batch slots (one suffix prefill
  each) and runs ONE decode step for the whole batch; finished
  requests retire and free their blocks the same iteration.  Because
  liveness is data (scratch-block rows), not shape, the decode step
  compiles ONCE per engine and admission/retirement never retrace.
- **int8 weight quantization** (``quantize.quantize_weights``) for
  weight-stream density, dequantized inside the jit.

The SLO/survival layer (docs/serving.md "SLOs, shedding, and
drain") rides the same loop: per-request TTFT/total **deadlines**
enforced on monotonic clocks (terminal ``expired``), client
**cancellation** (``cancel()`` / abandoned ``stream_request()``,
terminal ``cancelled``), **admission control** (bounded queue +
queued-token budget -> typed ``ServeRejectedError`` at ``submit()``),
graceful **drain** + atomic **snapshot/restore** of all in-flight
requests (greedy recompute makes the continuation token-identical,
SIGTERM wired to snapshot-then-drain), and a **decode-step
watchdog** dumping the flight recorder on budget overruns.  Every
new path is injectable: ``MXTPU_FAULT_SPEC`` scopes ``serve:step`` /
``serve:deadline`` / ``serve:queue`` next to ``serve:request``.

The decode loop's only device->host sync is the per-iteration token
read (enforced by ci/lint.py's host-sync rule over this module).
Telemetry rides the process registry: request/ token counters,
queue-wait / TTFT / per-token histograms, occupancy and
pool-utilization gauges.  ``MXTPU_FAULT_SPEC`` scope
``serve:request`` poisons the nth admission: the request is evicted
(state ``failed``) without touching its batchmates.
"""
import itertools
import os
import threading
import time
import weakref
from collections import deque

import numpy as np

from .. import resilience, telemetry, tracing
from ..utils.env import get_env
from ..utils.log import get_logger
from .block_table import BlockPool, BlockPoolExhausted
from .cache_manager import PrefixCache
from .quantize import quantize_weights
from .scheduler import (CANCELLED, EXPIRED, FAILED, FINISHED, QUEUED,
                        Request, RequestTooLargeError, Scheduler,
                        SchedulingError, ServeRejectedError)

__all__ = ["ServingEngine"]

SNAPSHOT_VERSION = 1

# process-unique engine ids: request ids restart at 0 per engine, so
# trace events carry (engine, rid) — a post-mortem dump spanning two
# engines must never conflate their requests
_ENGINE_IDS = itertools.count()


def _next_pow2(n):
    return 1 << max(0, int(n - 1)).bit_length()


# what a served model offers (docs/serving.md, "The paged protocol")
PAGED_PROTOCOL = ("_max_len", "n_layers", "_check_paged", "_paged_cache",
                  "_decode_weights", "_build_paged_prefill",
                  "_build_paged_step", "_decode_workspace_bytes")


class ServingEngine:
    """Continuous-batching decode engine over one model that offers
    the paged protocol (``TransformerLM``, ``LatentMoELM``).

    Parameters (env defaults in parentheses; docs/env_vars.md):

    model : an initialized model that offers the paged protocol
        (docs/serving.md): the row its cache holds for one token in
        one layer, a prefill builder, a decode-step builder, a
        context limit
    max_batch : concurrent decode slots (``MXTPU_SERVE_MAX_BATCH``)
    block_size : tokens per KV block (``MXTPU_SERVE_BLOCK_SIZE``)
    num_blocks : pool size incl. the reserved scratch block
        (``MXTPU_SERVE_NUM_BLOCKS``); pass ``"auto"`` to size the
        pool from memory-planner headroom — capacity minus weights
        and decode workspace (docs/memory.md), refusing with a typed
        error when the model alone cannot fit
    quantize : ``"off"`` or ``"int8"`` (``MXTPU_SERVE_QUANT``)
    prefix_cache : share prompt-prefix KV blocks across requests
        (``MXTPU_SERVE_PREFIX_CACHE``)
    keep_logits : retain each slot's last-step logits on the request
        (device array; for validation/debugging — never host-read by
        the engine)
    ttft_deadline / deadline : default per-request SLOs in seconds
        (``MXTPU_SERVE_TTFT_DEADLINE`` / ``MXTPU_SERVE_DEADLINE``;
        0 disables) — ``submit(..., ttft_deadline=, deadline=)``
        overrides per request
    queue_limit / queue_tokens : admission control
        (``MXTPU_SERVE_QUEUE_LIMIT`` / ``MXTPU_SERVE_QUEUE_TOKENS``;
        0 = unbounded): past either bound ``submit()`` sheds with a
        typed :class:`ServeRejectedError`
    step_timeout : decode-step watchdog budget in seconds
        (``MXTPU_SERVE_STEP_TIMEOUT``; 0 disables)
    max_len : the most positions one request may hold, prompt and
        new tokens together (default: the model's ``_max_len``).  It
        bounds a table row, the top prefill bucket, and what a
        decode program that gathers its context (the plain read) takes
        in for every slot

    Decoding is greedy (temperature-0) — the batch-invariant mode
    whose outputs are provably identical to sequential
    ``generate()``; sampling policies layer on later without
    touching the cache machinery.

    The engine is single-threaded: ``submit()`` may be called from
    anywhere, but ``step()``/``stream()``/``run()`` must be driven
    from one thread.
    """

    def __init__(self, model, max_batch=None, block_size=None,
                 num_blocks=None, quantize=None, prefix_cache=None,
                 keep_logits=False, ttft_deadline=None,
                 deadline=None, queue_limit=None, queue_tokens=None,
                 step_timeout=None, max_len=None):
        lacking = [a for a in PAGED_PROTOCOL if not hasattr(model, a)]
        if lacking:
            raise TypeError(
                "ServingEngine serves models that offer the paged "
                f"protocol (docs/serving.md); {type(model).__name__} "
                f"lacks {', '.join(lacking)}")
        model._check_paged()
        self.block_size = int(block_size if block_size is not None
                              else get_env("MXTPU_SERVE_BLOCK_SIZE"))
        raw_blocks = num_blocks if num_blocks is not None \
            else get_env("MXTPU_SERVE_NUM_BLOCKS")
        # num_blocks="auto": size the pool from planner headroom
        # (docs/memory.md) once the weights are settled below
        self.auto_blocks = (isinstance(raw_blocks, str)
                            and raw_blocks.lower() == "auto")
        self.num_blocks = 0 if self.auto_blocks else int(raw_blocks)
        self.max_batch = int(max_batch if max_batch is not None
                             else get_env("MXTPU_SERVE_MAX_BATCH"))
        if self.block_size < 1 or self.max_batch < 1:
            raise ValueError(
                f"bad serving config: block_size={self.block_size}, "
                f"max_batch={self.max_batch}")
        quantize = (get_env("MXTPU_SERVE_QUANT")
                    if quantize is None else quantize)
        if prefix_cache is None:
            prefix_cache = get_env("MXTPU_SERVE_PREFIX_CACHE")
        # SLO/survival knobs (docs/serving.md "SLOs, shedding, and
        # drain"); every deadline comparison is monotonic-clock
        # (lint-enforced — wall clock jumps must never expire work)
        self.ttft_deadline = float(
            ttft_deadline if ttft_deadline is not None
            else get_env("MXTPU_SERVE_TTFT_DEADLINE"))
        self.deadline = float(
            deadline if deadline is not None
            else get_env("MXTPU_SERVE_DEADLINE"))
        self.queue_limit = int(
            queue_limit if queue_limit is not None
            else get_env("MXTPU_SERVE_QUEUE_LIMIT"))
        self.queue_tokens = int(
            queue_tokens if queue_tokens is not None
            else get_env("MXTPU_SERVE_QUEUE_TOKENS"))
        self.step_timeout = float(
            step_timeout if step_timeout is not None
            else get_env("MXTPU_SERVE_STEP_TIMEOUT"))

        self.model = model
        self.max_len = int(model._max_len if max_len is None
                           else max_len)
        if not 1 <= self.max_len <= model._max_len:
            raise ValueError(
                f"max_len={self.max_len} is not within the model's "
                f"{model._max_len} positions")
        # one table row spans the context budget
        self.max_blocks = -(-self.max_len // self.block_size)
        self._sched = Scheduler(self.max_batch)
        self.keep_logits = bool(keep_logits)

        # weights settle BEFORE the pool: auto pool sizing needs the
        # real (possibly quantized) weight bytes on the chip
        wts = self._settled_weights(model)
        if quantize in ("int8", True):
            if not getattr(model, "_paged_int8", False):
                raise ValueError(
                    f"{type(model).__name__}'s paged programs do not "
                    "read int8 weights: quantize='off'")
            self._wts = quantize_weights(wts)
            self.quantized = True
        elif quantize in ("off", "", False, None):
            self._wts = wts
            self.quantized = False
        else:
            raise ValueError(
                f"quantize must be 'off' or 'int8', got {quantize!r}")

        import jax.numpy as jnp
        # the model's cache: what one token leaves in one layer, a
        # pool each (TransformerLM: a row of keys and one of values,
        # float32; LatentMoELM: one latent row in the weights' dtype)
        self.cache_spec = tuple(
            {**c, "shape": tuple(c["shape"]),
             "dtype": str(jnp.dtype(c["dtype"]))}
            for c in model._paged_cache())
        self.token_bytes = model.n_layers * sum(
            int(np.prod(c["shape"])) * jnp.dtype(c["dtype"]).itemsize
            for c in self.cache_spec)
        if self.auto_blocks:
            self.num_blocks = self._auto_num_blocks()
        if self.num_blocks < 1:
            raise ValueError(
                f"bad serving config: num_blocks={self.num_blocks}")
        self.pool = BlockPool(self.num_blocks, self.block_size)
        self.cache = PrefixCache(self.pool, enabled=prefix_cache)
        # one list of per-layer arrays a pool; the programs take and
        # return them in this order, right after the weights
        self._pools = tuple(
            [jnp.zeros((self.num_blocks, self.block_size) + c["shape"],
                       c["dtype"]) for _ in range(model.n_layers)]
            for c in self.cache_spec)

        self._step_fn = None
        self._prefill_fns = {}
        self.trace_counts = {}
        self._next_id = 0
        self._submit_lock = threading.Lock()
        self._completed = []        # terminal since last run()/drain()
        # SLO/survival state: live requests by id (cancel() target),
        # terminal-state counts for stats(), the drain latch, and
        # two cheap arm counters that keep the reap sweep off the
        # decode hot path when no deadline/cancel is pending
        self._live = {}
        self._terminal_counts = {}
        self._draining = False
        self._deadlines_armed = 0
        self._cancels_pending = 0
        # earliest armed deadline stamp: the reap sweep skips the
        # queue walk entirely until the clock reaches it (or a
        # cancel is pending); recomputed by every sweep
        self._deadline_next = float("inf")
        # the one request mid-transit between queue and slot
        # (_admit pop->place, _preempt clear->requeue): a SIGTERM
        # snapshot() interrupting that window must still see it —
        # it is in neither sched.waiting nor sched.slots
        self._in_transit = None
        # lock-free dirty bit for stream_request abandons: set with
        # a plain store from whatever thread GC runs the finalizer
        # on (cancel()'s lock would deadlock there); tells the reap
        # sweep to run even though _cancels_pending was not bumped
        self._abandon_flagged = False
        # flight recorder: compile attribution for the traced
        # builders, terminal per-request summaries for stats(), and
        # KV-pool bytes attributed in the device-memory gauges (via
        # a weakref so the process-wide provider table never pins a
        # dropped engine)
        self.engine_id = next(_ENGINE_IDS)
        # per-engine ledger site: jit caches are per-engine, so two
        # identically-configured engines genuinely compile twice —
        # a shared site would attribute the second as 'duplicate'
        self._ledger = tracing.compile_ledger(
            f"serving_engine:{self.engine_id}")
        self._req_summaries = deque(maxlen=1024)
        # serving lanes are static: name them once instead of
        # re-storing the same mapping per async event on the decode
        # path (set_lane_name takes the profiler lock)
        from .. import profiler
        profiler._profiler.set_lane_name(
            profiler.SERVE_QUEUE_LANE, "serve queue")
        for s in range(self.max_batch):
            profiler._profiler.set_lane_name(
                profiler.SERVE_SLOT_LANE0 + s, f"serve slot {s}")
        ref = weakref.ref(self)

        def _kv_arrays():
            eng = ref()
            if eng is None:
                return []
            return [a for pool in eng._pools for a in pool]

        self._mem_unregister = tracing.register_memory(
            "kv_pools", _kv_arrays, owner=self)
        tracing.install_signal_dump()

        # telemetry handles cached once (no-ops when disabled)
        self._m_requests = telemetry.counter("serving_requests_total")
        self._m_tokens = telemetry.counter("serving_tokens_total")
        self._m_prefill = telemetry.counter(
            "serving_prefill_tokens_total")
        self._m_prefill_padded = telemetry.counter(
            "serving_prefill_padded_tokens_total")
        self._m_hits = telemetry.counter(
            "serving_prefix_cache_hits_total")
        self._m_misses = telemetry.counter(
            "serving_prefix_cache_misses_total")
        self._m_preempt = telemetry.counter(
            "serving_preemptions_total")
        self._m_evict = telemetry.counter("serving_evictions_total")
        self._m_live_blocks = telemetry.counter(
            "serving_decode_blocks_live_total")
        self._m_allowed_blocks = telemetry.counter(
            "serving_decode_blocks_allowed_total")
        self._m_occ = telemetry.gauge("serving_batch_occupancy")
        self._m_util = telemetry.gauge(
            "serving_block_pool_utilization")
        self._h_wait = telemetry.histogram(
            "serving_queue_wait_seconds")
        self._h_ttft = telemetry.histogram("serving_ttft_seconds")
        self._h_tok = telemetry.histogram(
            "serving_token_latency_seconds")
        self._m_rejected = telemetry.counter(
            "serving_rejected_total")
        self._m_expired = telemetry.counter("serving_expired_total")
        self._m_cancelled = telemetry.counter(
            "serving_cancelled_total")
        self._m_drains = telemetry.counter("serving_drains_total")
        self._m_qdepth = telemetry.gauge("serving_queue_depth")
        self._m_qtokens = telemetry.gauge(
            "serving_queued_prompt_tokens")
        # each pool's bytes under its own name, beside the
        # device_bytes_kv_pools they are all counted in
        for c, pool in zip(self.cache_spec, self._pools):
            telemetry.gauge(f"serving_pool_{c['name']}_bytes").set(
                sum(a.nbytes for a in pool))
        # a routed layer's statistics, where the model has one: they
        # come back behind the tokens, in the same fetch
        self._m_moe = [telemetry.counter(f"serving_moe_{n}_total")
                       for n in ("routed_rows", "padded_rows",
                                 "experts_touched", "layer_steps")]

    # ---------------------------------------------------------- setup
    def _auto_num_blocks(self):
        """Size the KV pool from planner headroom (docs/memory.md):
        usable device capacity (MXTPU_HBM_BYTES override honored,
        MXTPU_MEM_GATE_MARGIN reserved) minus the settled weights and
        a per-step decode workspace (hidden states + logits), divided
        by per-block bytes of the model's own row — capped at a full
        context row for every slot plus the scratch block, so tiny
        models never hoard the chip.  Refuses with a typed
        MemoryPlanError when the model alone leaves no room for one
        block per slot."""
        from ..perf import memory_planner as mp
        from ..perf.device_db import headroom, hbm_capacity
        wts_bytes = mp.tree_bytes(self._wts)
        # decode workspace: one step's logits + residual stream per
        # slot (fp32), the transient XLA scratch next to the pools
        workspace = self.model._decode_workspace_bytes(self.max_batch)
        per_block = float(self.token_bytes * self.block_size)
        avail = headroom(wts_bytes + workspace)
        floor = self.max_batch + 1   # one block per slot + scratch
        if avail < per_block * floor:
            from ..resilience import MemoryPlanError
            plan = mp.MemoryPlan(
                params=wts_bytes, activations=workspace,
                kv_pool=per_block * floor,
                meta={"site": "serving_engine",
                      "num_blocks": floor,
                      "quantized": self.quantized})
            raise MemoryPlanError("serving_engine", plan,
                                  capacity=hbm_capacity())
        n = int(avail // per_block)
        cap = self.max_batch * self.max_blocks + 1
        n = min(n, cap)
        plan = mp.MemoryPlan(
            params=wts_bytes, activations=workspace,
            kv_pool=per_block * n,
            meta={"site": "serving_engine", "num_blocks": n,
                  "quantized": self.quantized})
        mp._publish_plan(plan)
        import logging
        logging.getLogger("mxtpu.memory").info(
            "serving KV pool auto-sized: %d blocks (%s)", n,
            plan.describe())
        return n

    @staticmethod
    def _settled_weights(model):
        from ..gluon.parameter import DeferredInitializationError
        try:
            return model._decode_weights()
        except DeferredInitializationError:
            # deferred-init params (LayerNorm shapes): settle with a
            # tiny probe forward, exactly as generate() does
            import jax.numpy as jnp

            from .. import autograd, ndarray as nd
            with autograd.pause():
                model.forward(
                    nd.NDArray(jnp.zeros((1, 1), jnp.int32)))
            return model._decode_weights()

    def _counted_jit(self, name, fn, signature):
        import jax

        def traced(*args):
            # runs at TRACE time only: the regression tests assert
            # admission/retirement replay the compiled step
            self.trace_counts[name] = \
                self.trace_counts.get(name, 0) + 1
            return fn(*args)

        # the program's name on a device trace's "XLA Modules" line
        # (jit_serve_decode, jit_serve_prefill_<bucket>)
        traced.__name__ = traced.__qualname__ = f"serve_{name}"

        # donate the pools (right after the weights in both the
        # prefill and the step signature): the compiled call updates
        # the cache IN PLACE instead of copying every pool array out
        # per token — the engine always rebinds self._pools from the
        # outputs, so the consumed buffers are never reused
        jfn = jax.jit(traced, donate_argnums=tuple(
            range(1, 1 + len(self._pools))))

        def called(*args):
            # a call that ran the Python trace just compiled: record
            # the retrace with its wall time + signature attribution
            # (an unexpected re-trace of the decode step is exactly
            # the storm MXTPU_COMPILE_BUDGET watches for)
            before = self.trace_counts.get(name, 0)
            t0 = time.monotonic()
            out = jfn(*args)
            if self.trace_counts.get(name, 0) > before:
                self._ledger.record(signature, time.monotonic() - t0)
            return out

        return called

    def _get_step_fn(self):
        if self._step_fn is None:
            describe = getattr(self.model, "_paged_read", None)
            if describe is not None:
                # built where it is first called, so under the matmul
                # precision its trace will see
                import jax
                platform = jax.default_backend()
                tracing.trace_event(
                    "serve_paged_read", engine=self.engine_id,
                    platform=platform, max_batch=self.max_batch,
                    max_blocks=self.max_blocks,
                    block_size=self.block_size,
                    **describe(self.block_size, platform))
            self._step_fn = self._counted_jit(
                "decode", self.model._build_paged_step(
                    self.max_batch, self.max_blocks,
                    self.block_size),
                {"builder": "decode",
                 "static_arg": (self.max_batch, self.max_blocks,
                                self.block_size)})
        return self._step_fn

    def _get_prefill_fn(self, suffix_len):
        # pow2 buckets, floored at one block: a prefix-cache hit can
        # shrink the suffix to a couple of tokens, and compiling a
        # dedicated tiny executable per length would cost far more
        # than the padded rows it saves
        bucket = min(max(_next_pow2(suffix_len),
                         _next_pow2(self.block_size)),
                     _next_pow2(self.max_len))
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            fn = self._prefill_fns[bucket] = self._counted_jit(
                f"prefill_{bucket}", self.model._build_paged_prefill(
                    bucket, self.max_blocks, self.block_size),
                {"builder": "prefill", "shape": (bucket,),
                 "static_arg": (self.max_blocks, self.block_size)})
        return bucket, fn

    # ------------------------------------------------------------- API
    def _check_servable(self, n_tokens, max_new):
        """Raise :class:`RequestTooLargeError` when a request of
        ``n_tokens`` prompt + ``max_new`` generated tokens can NEVER
        be served by this engine — queueing it would hang the
        schedule forever (docs/serving.md)."""
        total = n_tokens + max_new
        if total > self.max_len:
            raise RequestTooLargeError(
                f"prompt+new = {total} exceeds max_len "
                f"{self.max_len}")
        need = -(-total // self.block_size)
        if need > min(self.max_blocks, self.pool.capacity):
            raise RequestTooLargeError(
                f"request needs {need} blocks but the pool serves "
                f"at most {min(self.max_blocks, self.pool.capacity)}"
                " per sequence — raise MXTPU_SERVE_NUM_BLOCKS or "
                "shrink the request")

    def _reject(self, n_tokens, reason):
        """Shed one submission: exactly one terminal trace event
        (queue context attached — a rejected request never waited,
        the event says what it would have waited behind), counters,
        then the typed raise."""
        depth = len(self._sched.waiting)
        qtok = self._sched.queued_tokens
        self._m_rejected.inc()
        self._terminal_counts["rejected"] = \
            self._terminal_counts.get("rejected", 0) + 1
        tracing.trace_event(
            "serve_reject", engine=self.engine_id,
            prompt_tokens=n_tokens, reason=reason,
            queue_depth=depth, queued_tokens=qtok)
        raise ServeRejectedError(
            f"request rejected ({reason}): queue depth {depth}"
            f"/{self.queue_limit or 'inf'}, queued tokens {qtok}"
            f"/{self.queue_tokens or 'inf'} — shedding keeps "
            "admitted requests' latency bounded (docs/serving.md)")

    def submit(self, tokens, max_new_tokens, eos_id=None,
               ttft_deadline=None, deadline=None):
        """Enqueue a prompt; returns its :class:`Request` handle.

        ``tokens`` is a 1D int sequence (list / numpy / NDArray).
        The handle's ``generated`` list fills as the engine runs
        (drive it via :meth:`step`, :meth:`stream` or :meth:`run`).

        ``ttft_deadline`` / ``deadline`` (seconds; default the
        engine's env-configured SLOs, 0/None = none) bound first
        token and total completion — a request past either expires
        (state ``expired``, blocks freed) instead of occupying the
        engine.  Raises :class:`RequestTooLargeError` when the
        request can never fit the pool/context, and
        :class:`ServeRejectedError` when admission control sheds it
        (bounded queue, token budget, or draining engine)."""
        if hasattr(tokens, "asnumpy"):
            tokens = tokens.asnumpy()
        toks = [int(t) for t in np.asarray(tokens).ravel()]
        max_new = int(max_new_tokens)
        if not toks:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1 (got {max_new})")
        self._check_servable(len(toks), max_new)
        if ttft_deadline is None:
            ttft_deadline = self.ttft_deadline
        if deadline is None:
            deadline = self.deadline
        with self._submit_lock:     # submit() may race across threads
            # admission control: shed at the door — a bounded queue
            # turns overload into fast typed failures instead of
            # unbounded TTFT collapse.  Preemption requeues bypass
            # this (push_front): they were already admitted.
            if self._draining:
                self._reject(len(toks), "draining")
            if self.queue_limit > 0 and \
                    len(self._sched.waiting) >= self.queue_limit:
                self._reject(len(toks), "queue_limit")
            if self.queue_tokens > 0 and \
                    self._sched.queued_tokens + len(toks) \
                    > self.queue_tokens:
                self._reject(len(toks), "queue_tokens")
            try:
                # injectable shedding: MXTPU_FAULT_SPEC
                # serve:queue:N:error rejects the Nth submission
                resilience.inject("serve", "queue")
            except resilience.TransientError:
                self._reject(len(toks), "injected")
            req = Request(self._next_id, toks, max_new,
                          eos_id=eos_id)
            self._next_id += 1
            now = time.monotonic()
            try:
                # injectable SLO breach: serve:deadline:N:error
                # forces the Nth submission to expire at the next
                # engine iteration, whatever its configured deadline
                resilience.inject("serve", "deadline")
            except resilience.TransientError:
                req.deadline_ts = now - 1.0
            else:
                if ttft_deadline and ttft_deadline > 0:
                    req.ttft_deadline_ts = now + float(ttft_deadline)
                if deadline and deadline > 0:
                    req.deadline_ts = now + float(deadline)
            if req.ttft_deadline_ts is not None \
                    or req.deadline_ts is not None:
                self._deadlines_armed += 1
                self._deadline_next = min(self._deadline_next,
                                          self._next_deadline(req))
            # lifecycle + async events fire BEFORE the scheduler can
            # see the request: once added, a concurrent engine
            # thread may admit it immediately, and serve_admit must
            # never carry a lower seq than serve_enqueue
            tracing.trace_event("serve_enqueue", rid=req.id,
                                engine=self.engine_id,
                                prompt_tokens=len(toks),
                                max_new_tokens=max_new)
            self._prof_async("b", "request", req)
            self._prof_async("b", "queue_wait", req)
            self._live[req.id] = req
            self._sched.add(req)
            self._m_qdepth.set(len(self._sched.waiting))
            self._m_qtokens.set(self._sched.queued_tokens)
        self._m_requests.inc()
        return req

    def cancel(self, rid):
        """Request cancellation of a live request by id (thread-safe;
        clients may call it from any thread, including a stream
        consumer that lost interest).  Honored at the next engine
        iteration: the request reaches terminal state ``cancelled``
        with its partial output retained and every pool block freed
        — cancellation can never leak blocks.  Returns True when the
        request was live and is now marked; False when unknown or
        already terminal."""
        with self._submit_lock:
            req = self._live.get(rid)
            if req is None or req.done or req.cancel_requested:
                return False
            req.cancel_requested = True
            req.cancel_counted = True
            self._cancels_pending += 1
            return True

    def has_work(self):
        """Whether driving the engine can still make progress: any
        request queued/running — or, while draining, only the
        RUNNING batch.  Queued requests are frozen for
        :meth:`snapshot` once drain latches; reporting them here
        would spin a ``while engine.has_work(): engine.step()``
        driver forever on work admission will never start."""
        return self._has_loop_work()

    def _has_loop_work(self):
        """What drives stream()/run(): everything, or — while
        draining — only the running batch (queued requests are
        deliberately left for snapshot(), never admitted)."""
        if self._draining:
            return self._sched.any_running()
        return self._sched.has_work()

    def step(self):
        """One continuous-batching iteration: reap (cancellations +
        expired deadlines, blocks freed same-iteration) -> admit ->
        grow -> decode -> retire.  Returns the ``(request,
        token_id)`` events emitted this iteration."""
        events = []
        with telemetry.span("serve_step",
                            running=self._sched.n_running(),
                            waiting=len(self._sched.waiting)) as sp:
            with telemetry.span("serve_reap"):
                self._reap()
            self._admit(events)
            if self._sched.any_running():
                with telemetry.span("serve_grow"):
                    self._grow()
            if self._sched.any_running():
                self._decode_once(events)
            self._m_occ.set(self._sched.n_running() / self.max_batch)
            self._m_util.set(self.pool.utilization())
            self._m_qdepth.set(len(self._sched.waiting))
            self._m_qtokens.set(self._sched.queued_tokens)
            sp.set(emitted=len(events))
        return events

    def _count_moe(self, stats, decode=False):
        """What follows the tokens in a program's ``next``: the
        routed layers' statistics summed over the layers (rows the
        grouped product multiplied, rows of them padding, distinct
        experts read, routed layers).  Rows count for every program;
        experts touched a layer is a decode step's number.  Returns
        the experts touched."""
        if not len(stats):
            return None
        rows, padded, touched, layers = (int(v) for v in stats)
        self._m_moe[0].inc(rows)
        self._m_moe[1].inc(padded)
        if decode:
            self._m_moe[2].inc(touched)
            self._m_moe[3].inc(layers)
        return touched

    def stream(self):
        """Drive the engine, yielding ``(request, token_id)`` events
        as they are produced, until all submitted work drains (or,
        while draining, until the running batch finishes)."""
        while self._has_loop_work():
            for ev in self.step():
                yield ev

    def stream_request(self, req):
        """Drive the engine yielding ``req``'s tokens only — the
        per-client streaming view.  ABANDONING the generator (break
        / ``close()`` / GC — started or not) cancels the request: a
        client that hung up must not keep burning decode slots and
        KV blocks.  The abandon path only FLAGS the cancellation,
        with plain attribute stores — a GC finalizer may run it on
        any thread, even reentrantly inside ``step()`` or under the
        submit lock, where taking a lock or mutating scheduler/pool
        state would deadlock or corrupt the iteration — and the
        next engine iteration finalizes it as CANCELLED, freeing
        its blocks.  A NORMAL exit (the request finished, or drain
        latched and the loop ran out of work) cancels nothing: a
        drained-but-queued request belongs to :meth:`snapshot`."""
        # shared cell, not a local: a generator abandoned before its
        # first next() never enters the body (GEN_CREATED close/GC
        # runs no code), so the body's finally cannot cover that
        # case — the weakref.finalize on the generator object does,
        # and the cell tells it a normal exhaustion already happened
        state = {"exhausted": False}

        def _flag():
            if not state["exhausted"] and not req.done:
                req.cancel_requested = True
                self._abandon_flagged = True

        gen = self._stream_gen(req, state, _flag)
        weakref.finalize(gen, _flag)
        return gen

    def _stream_gen(self, req, state, flag):
        # yield from a CURSOR over req.generated, not from this
        # generator's own step() events: continuous batching means
        # other drivers (run()/stream()/a sibling stream_request)
        # may decode this request's tokens — append-only list, so
        # the cursor never misses one, whoever produced it
        sent = 0
        try:
            while True:
                while sent < len(req.generated):
                    yield req.generated[sent]
                    sent += 1
                if req.done or not self._has_loop_work():
                    break
                self.step()
            state["exhausted"] = True
        finally:
            flag()

    def run(self):
        """Drain everything; returns ``{request_id: full token
        list}`` for every request that reached a terminal state
        during this call (failed / expired / cancelled ones included
        with their partial output — check ``request.state``)."""
        for _ev in self.stream():
            pass
        done, self._completed = self._completed, []
        return {req.id: req.tokens for req in done}

    def drain(self, run=True):
        """Graceful shutdown, phase one: stop admission (subsequent
        ``submit()`` calls shed with ``ServeRejectedError``), keep
        queued requests queued — they belong to :meth:`snapshot` —
        and, with ``run=True``, finish the currently RUNNING batch.
        Returns the terminal requests collected since the last
        ``run()``/``drain()`` as ``{id: tokens}``.  Idempotent."""
        self._latch_drain()
        if run:
            while self._sched.any_running():
                self.step()
        done, self._completed = self._completed, []
        return {req.id: req.tokens for req in done}

    def _latch_drain(self):
        """Latch admission off (idempotent): counter + the one
        ``serve_drain`` event fire on the first latch, however it
        happens — ``drain()`` or the SIGTERM handler.  Touches no
        ``_completed`` state, so it is safe from a signal handler
        interrupting ``run()``."""
        if self._draining:
            return
        self._draining = True
        self._m_drains.inc()
        tracing.trace_event(
            "serve_drain", engine=self.engine_id,
            running=self._sched.n_running(),
            queue_depth=len(self._sched.waiting))

    # -------------------------------------------- snapshot / restore
    def _snapshot_request(self, req, now):
        """One in-flight request's resumable state.  Deadlines are
        persisted as REMAINING seconds (monotonic stamps are
        meaningless in another process); a negative remainder means
        the restored request expires on its first iteration, which
        is exactly the SLO truth."""
        # observability parity across the crash: a QUEUED request's
        # wait segment is still open — close it into the persisted
        # total exactly like every terminal path does, or the
        # restored lifecycle under-reports its pre-crash wait
        wait = req.queue_wait_s
        if req.state == QUEUED:
            wait += now - req.enqueue_ts
        return {
            "id": req.id,
            "prompt": list(req.prompt),
            "generated": list(req.generated),
            "max_new_tokens": req.max_new_tokens,
            "eos_id": req.eos_id,
            "queue_wait_s": wait,
            "prefill_s": req.prefill_s,
            "preemptions": req.preemptions,
            "ttft_done": req.first_token_ts is not None,
            "ttft_remaining_s": (
                req.ttft_deadline_ts - now
                if req.ttft_deadline_ts is not None else None),
            "deadline_remaining_s": (
                req.deadline_ts - now
                if req.deadline_ts is not None else None),
        }

    def snapshot(self, path=None):
        """Persist every in-flight request (running by admission
        order first, then the waiting queue in order) so a fresh
        engine can :meth:`restore` them.  A request is fully
        reconstructible from prompt + generated tokens: greedy
        recompute (the same property preemption relies on) makes the
        restored continuation token-identical.

        Returns the snapshot dict; with ``path`` it is also written
        via ``resilience.atomic_save`` (+ CRC32 sidecar), so a
        SIGTERM-time snapshot a reader observes is whole or absent,
        never torn.  Safe to call from a signal handler interrupting
        the engine thread: only host-side Python state is read, each
        request's ``generated`` list is append-only, and a request
        the signal caught mid-transit between queue and slot
        (``_in_transit``) is captured too — it is in neither
        ``waiting`` nor ``slots`` during that window."""
        now = time.monotonic()
        running = sorted(
            (r for r in list(self._sched.slots) if r is not None),
            key=lambda r: r.admit_seq)
        transit = self._in_transit
        # index-walk, not iteration/safe_list: client threads only
        # APPEND to the waiting deque (removal is engine-loop-only,
        # and a signal handler freezes that very thread), so walking
        # by index yields a consistent snapshot where an iterator
        # would raise on a concurrent append — and a degrade-to-
        # empty fallback would silently drop the whole queue from
        # the crash-resume file
        waiting = []
        i = 0
        while True:
            try:
                waiting.append(self._sched.waiting[i])
            except IndexError:
                break
            i += 1
        # cancel-flagged requests are excluded (the client already
        # hung up — a restore must not resurrect them); the id
        # dedup covers a transit pointer that already landed back
        # in a slot or the queue
        reqs, seen = [], set()
        # the _live straggler sweep is the safety net: if the engine
        # loop runs on a DIFFERENT thread than this snapshot (not
        # the documented signal-handler-freezes-the-loop case), a
        # concurrently-popped request can be missing from all three
        # views above for an instant — _live holds every non-
        # terminal request regardless, so none can vanish from the
        # crash-resume file (it merely lands at the queue's tail)
        for r in (list(running)
                  + ([transit] if transit is not None else [])
                  + waiting
                  + list(self._live.copy().values())):
            if r.id in seen or r.done or r.cancel_requested:
                continue
            seen.add(r.id)
            reqs.append(self._snapshot_request(r, now))
        snap = {
            "version": SNAPSHOT_VERSION,
            "engine": {"max_batch": self.max_batch,
                       "block_size": self.block_size,
                       "num_blocks": self.num_blocks,
                       "prefix_cache": self.cache.enabled,
                       "quantize": ("int8" if self.quantized
                                    else "off"),
                       "max_len": self.max_len,
                       "cache": [dict(c) for c in self.cache_spec]},
            "next_id": self._next_id,
            "requests": reqs,
        }
        tracing.trace_event("serve_snapshot", engine=self.engine_id,
                            requests=len(reqs),
                            path=str(path) if path else None)
        if path is not None:
            import pickle
            resilience.atomic_save(
                path, lambda f: pickle.dump(snap, f))
        return snap

    @classmethod
    def restore(cls, model, snapshot, **engine_kw):
        """Build a fresh engine and re-queue every request of a
        :meth:`snapshot` (a path, or the dict itself).  Restored
        requests continue by greedy recompute — re-admission
        prefills ``prompt + generated``, exactly the preemption
        path — so completed outputs are token-identical to an
        uninterrupted run.  Engine geometry defaults to the
        snapshot's; explicit ``engine_kw`` overrides win, and a
        request the new geometry can never serve fails loudly at
        admission (typed, per-request) instead of hanging the
        schedule."""
        if isinstance(snapshot, (str, os.PathLike)):
            import pickle
            path = os.fspath(snapshot)
            snapshot = resilience.decode_or_corrupt(
                path, lambda: pickle.loads(
                    resilience.read_validated_bytes(path)))
        if not isinstance(snapshot, dict) or \
                snapshot.get("version") != SNAPSHOT_VERSION or \
                "requests" not in snapshot:
            raise resilience.CheckpointCorruptError(
                "not a serving snapshot (or an incompatible "
                f"version): {snapshot!r:.80}")
        cfg = snapshot.get("engine", {})
        for key in ("max_batch", "block_size", "num_blocks",
                    "prefix_cache", "quantize", "max_len"):
            if cfg.get(key) is not None:
                engine_kw.setdefault(key, cfg[key])
        eng = cls(model, **engine_kw)
        for entry in snapshot["requests"]:
            eng.resubmit(entry)
        with eng._submit_lock:
            eng._next_id = max(
                eng._next_id, int(snapshot.get("next_id", 0)))
        tracing.trace_event("serve_restore", engine=eng.engine_id,
                            requests=len(snapshot["requests"]))
        return eng

    def resubmit(self, entry, redispatch=False):
        """Re-admit ONE request in :meth:`_snapshot_request` entry
        form — the shared re-admission path under :meth:`restore`
        (crash resume) and the fleet router's failover re-dispatch
        (serving/router.py ships exactly this schema over rpc.py to
        a surviving replica).  Continues by greedy recompute:
        re-admission prefills ``prompt + generated``, so the
        completed output is token-identical to an uninterrupted run.

        Bypasses admission control deliberately — the request was
        already admitted once (at the original engine or fleet-wide
        at the router); shedding it here would turn one failure into
        two.  Deadlines in the entry are REMAINING seconds and are
        re-armed against this process's monotonic clock; a request
        whose first token already shipped (``ttft_done``) does not
        re-arm TTFT and never re-emits ``serve_first_token``
        (lifecycle parity: one first token per request, ever).
        Returns the :class:`Request`."""
        now = time.monotonic()
        complete = False   # retired OUTSIDE the lock: _finalize takes it
        with self._submit_lock:
            req = Request(int(entry["id"]), entry["prompt"],
                          entry["max_new_tokens"],
                          eos_id=entry.get("eos_id"))
            req.generated = [int(t)
                             for t in entry.get("generated", [])]
            req.queue_wait_s = float(
                entry.get("queue_wait_s", 0.0))
            req.prefill_s = float(entry.get("prefill_s", 0.0))
            req.preemptions = int(entry.get("preemptions", 0))
            rem = entry.get("deadline_remaining_s")
            if rem is not None:
                req.deadline_ts = now + float(rem)
            rem = entry.get("ttft_remaining_s")
            # a request whose first token shipped pre-crash met
            # its TTFT SLO; the re-prefill must not re-arm it —
            # and must not re-emit serve_first_token or observe
            # a second TTFT sample (lifecycle parity: one first
            # token per request, ever)
            if entry.get("ttft_done"):
                req.first_token_ts = now
                req.last_token_ts = now
            elif rem is not None:
                req.ttft_deadline_ts = now + float(rem)
            tracing.trace_event(
                "serve_enqueue", rid=req.id,
                engine=self.engine_id,
                prompt_tokens=len(req.prompt),
                max_new_tokens=req.max_new_tokens,
                restored=True, redispatch=bool(redispatch),
                generated_tokens=len(req.generated))
            self._prof_async("b", "request", req)
            self._prof_async("b", "queue_wait", req)
            self._live[req.id] = req
            self._m_requests.inc()
            self._next_id = max(self._next_id, req.id + 1)
            # a snapshot can catch a request BETWEEN its last
            # generated token and its same-iteration retirement
            # (req.done latches at _retire): that request is
            # already complete — re-queueing it would decode
            # one token past its budget/EOS and break the
            # token-identical resume guarantee
            if (len(req.generated) >= req.max_new_tokens
                    or (req.eos_id is not None and req.generated
                        and req.generated[-1] == req.eos_id)):
                complete = True
            else:
                if req.ttft_deadline_ts is not None \
                        or req.deadline_ts is not None:
                    self._deadlines_armed += 1
                    self._deadline_next = min(
                        self._deadline_next,
                        self._next_deadline(req))
                self._sched.add(req)
        if complete:
            self._retire(req)   # exactly-one-terminal parity holds
        return req

    def take_completed(self):
        """Pop and return the terminal :class:`Request` objects
        collected since the last ``run()``/``drain()``/
        ``take_completed()`` — WITHOUT latching drain.  The fleet
        replica's serve loop (serving/replica.py) consumes terminals
        incrementally this way while staying open for new
        dispatches; ``run()`` and ``drain()`` keep their
        consume-on-return semantics."""
        with self._submit_lock:
            done, self._completed = self._completed, []
        return done

    def install_sigterm(self, snapshot_path, drain=True):
        """Wire SIGTERM to snapshot-then-drain: the handler writes
        an atomic :meth:`snapshot` of every in-flight request to
        ``snapshot_path``, then latches :meth:`drain` mode so the
        loop finishes the running batch and ``run()``/``stream()``
        return (the process exits normally — the signal is consumed).
        With ``drain=False`` the previous SIGTERM disposition runs
        instead right after the snapshot (default disposition:
        process dies — the crash-resume flavor; a fresh process
        :meth:`restore`\\ s the snapshot).

        Main-thread only (signal.signal's rule); returns False
        when it cannot install.  Chains whatever PYTHON handler was
        there — tracing.install_signal_dump's post-mortem, another
        engine's snapshot hook — on every path; with ``drain=True``
        only the default-disposition re-raise is suppressed (it
        would kill the process drain means to let exit — though a
        chained handler that itself escalates still terminates,
        with snapshot and dump on disk).  Falls back to the
        previous disposition entirely once the engine is garbage-
        collected (the handler only holds a weakref; it must never
        consume SIGTERM on behalf of an engine that no longer
        exists)."""
        import signal as _signal
        if threading.current_thread() is not threading.main_thread():
            return False
        prev = _signal.getsignal(_signal.SIGTERM)
        eng_ref = weakref.ref(self)

        def handler(num, frame):
            eng = eng_ref()
            if eng is not None:
                try:
                    eng.snapshot(snapshot_path)
                except Exception:   # a torn dump must not mask the
                    pass            # signal's actual handling
                try:
                    eng._latch_drain()  # drains_total counts SIGTERM
                except Exception:       # the latch must hold even if
                    eng._draining = True    # telemetry raises
                if drain:
                    # consume the signal for THIS engine's graceful
                    # exit, but still run any chained Python handler
                    # first — another engine's snapshot hook or
                    # tracing's post-mortem dump must not be
                    # silenced by whoever installed last.  Only the
                    # default-disposition re-raise is suppressed
                    # (that would kill the process drain means to
                    # let exit); a chained handler that itself
                    # escalates leaves the snapshot + dump behind —
                    # the crash-resume flavor with artifacts.
                    if callable(prev):
                        prev(num, frame)
                    return
                # drain=False: fall through to the previous
                # disposition right after the snapshot
            # engine already gone (or drain=False): the previous
            # disposition must run — a dead weakref consuming every
            # SIGTERM would make the process unkillable by anything
            # short of SIGKILL
            if callable(prev):
                prev(num, frame)
            elif prev == _signal.SIG_IGN:
                return
            else:
                _signal.signal(num, _signal.SIG_DFL)
                _signal.raise_signal(num)

        try:
            _signal.signal(_signal.SIGTERM, handler)
        except (ValueError, OSError):
            return False
        return True

    # ------------------------------------------------------ internals
    def _alloc(self, n):
        """Pool alloc with prefix-cache eviction as the fallback."""
        try:
            return self.pool.alloc(n)
        except BlockPoolExhausted:
            self.cache.evict(n - self.pool.num_free)
            return self.pool.alloc(n)       # may re-raise

    def _admit(self, events):
        """Fill free slots from the waiting queue; one suffix
        prefill per admission (prefix-cache hits skip the shared
        blocks)."""
        if self._draining:
            return      # drain(): queued requests belong to snapshot()
        while self._sched.has_waiting():
            slot = self._sched.free_slot()
            if slot is None:
                return
            # publish to the snapshot pointer BEFORE popping: a
            # signal landing between the two statements sees the
            # request in both places (id-dedup) — after a bare pop
            # it would be in neither.  Visible until placed,
            # requeued, or terminal (terminals filter on req.done).
            self._in_transit = self._sched.waiting[0]
            with telemetry.span("serve_admit",
                                rid=self._in_transit.id,
                                slot=slot) as sp_admit:
                if not self._admit_one(slot, events, sp_admit):
                    return

    def _admit_one(self, slot, events, sp_admit):
        """One admission, from the pop to the first token appended.
        False where the queue's head has to wait for frees."""
        import jax
        import jax.numpy as jnp
        req = self._sched.pop_waiting()
        try:
            resilience.inject("serve", "request")
        except resilience.TransientError as exc:
            self._fail(req, exc)
            return True
        try:
            # re-check at admission: a snapshot restored into a
            # smaller pool/context must fail THAT request loudly,
            # not hang the schedule (submit() already vets fresh
            # submissions; preemption cannot grow the bound)
            self._check_servable(len(req.prompt),
                                 req.max_new_tokens)
        except RequestTooLargeError as exc:
            self._fail(req, exc)
            return True
        toks = req.tokens
        matched, n_cached = self.cache.match(toks)
        need = -(-len(toks) // self.block_size) - len(matched)
        try:
            fresh = self._alloc(need)
        except BlockPoolExhausted:
            if matched:
                self.pool.free(matched)     # release the match
            self._sched.push_front(req)
            self._in_transit = None
            if not self._sched.any_running():
                raise SchedulingError(
                    f"request {req.id} needs {need} fresh "
                    "blocks but the pool cannot ever provide "
                    "them — raise MXTPU_SERVE_NUM_BLOCKS")
            return False                    # wait for frees
        req.admit_ts = time.monotonic()
        # per-segment wait: a preempted request's requeue
        # restarted the clock, so re-admission must not count
        # its earlier prefill/decode time as queue wait
        wait = req.admit_ts - req.enqueue_ts
        req.queue_wait_s += wait
        self._h_wait.observe(wait)
        self._m_hits.inc(n_cached)
        self._m_misses.inc(len(toks) - n_cached)
        req.block_ids = matched + fresh
        self._sched.place(req, slot)
        self._in_transit = None
        sp_admit.set(cached_tokens=n_cached)
        tracing.trace_event(
            "serve_admit", rid=req.id, engine=self.engine_id,
            slot=slot,
            blocks=len(req.block_ids), cached_tokens=n_cached,
            queue_wait_s=round(wait, 6),
            preemptions=req.preemptions)
        self._prof_async("e", "queue_wait", req)
        self._prof_async("b", "prefill", req)

        suffix = toks[n_cached:]
        bucket, fn = self._get_prefill_fn(len(suffix))
        suf = np.zeros(bucket, np.int32)
        suf[:len(suffix)] = suffix
        row = np.zeros(self.max_blocks, np.int32)
        row[:len(req.block_ids)] = req.block_ids
        row, suf = jnp.asarray(row), jnp.asarray(suf)
        with telemetry.span("serve_prefill", rid=req.id,
                            tokens=len(suffix),
                            bucket=bucket) as sp_pre:
            *pools, nxt, logits = fn(
                self._wts, *self._pools,
                row, np.int32(n_cached), suf, np.int32(len(suffix)))
            self._pools = tuple(pools)
            # completion barrier, not a transfer: dispatching the
            # next call while its DONATED pool buffers are still
            # pending hits a pathological slow path (~7x) in the
            # runtime's donation bookkeeping
            jax.block_until_ready(self._pools[0])
        req.prefill_s += sp_pre.elapsed
        tracing.trace_event(
            "serve_prefill", rid=req.id, engine=self.engine_id,
            slot=slot,
            suffix_tokens=len(suffix), bucket=bucket,
            seconds=round(sp_pre.elapsed, 6))
        self._prof_async("e", "prefill", req)
        self._prof_async("b", "decode", req)
        self._m_prefill.inc(len(suffix))
        self._m_prefill_padded.inc(bucket)
        if self.keep_logits:
            req.logits = logits
        # register this stream's full blocks for future sharing
        self.cache.insert(toks, req.block_ids)
        req.n_past = len(toks)
        with telemetry.span("serve_token_fetch"):
            nxt = np.asarray(nxt).reshape(-1)  # sync-ok: first-token read seeds the decode loop
        self._count_moe(nxt[1:])
        self._append_token(req, int(nxt[0]), events)
        return True

    def _grow(self):
        """Ensure every runner owns the block its next position
        writes into; preempt the latest-admitted runner on
        exhaustion."""
        bs = self.block_size
        for req in sorted(self._sched.running(),
                          key=lambda r: r.admit_seq):
            if req.done or req.slot is None:
                continue        # preempted earlier in this pass
            if req.n_past // bs < len(req.block_ids):
                continue
            while True:
                try:
                    req.block_ids += self._alloc(1)
                    break
                except BlockPoolExhausted:
                    victim = self._sched.latest_running()
                    if victim is req and self._sched.n_running() == 1:
                        # the pool cannot hold this one sequence:
                        # fail THE REQUEST loudly (typed, terminal,
                        # blocks freed) instead of raising out of
                        # step() or — worse — spinning forever
                        self._fail(req, SchedulingError(
                            "block pool exhausted with a single "
                            "running request — the pool cannot hold "
                            "one full sequence; raise "
                            "MXTPU_SERVE_NUM_BLOCKS"))
                        break
                    self._preempt(victim)
                    if victim is req:
                        break               # we preempted ourselves

    def _preempt(self, req):
        """Free a runner's blocks and re-queue it (front).  Its
        generated tokens survive; re-admission re-prefills
        prompt+generated (cheap again once the prefix cache holds
        the shared blocks)."""
        freed = len(req.block_ids)
        self._in_transit = req      # out of the slot, not yet queued
        self._sched.clear(req)
        if req.block_ids:
            self.pool.free(req.block_ids)
        req.block_ids = []
        req.n_past = 0
        req.state = QUEUED
        req.preemptions += 1
        self._m_preempt.inc()
        # a preempted runner is queued again: its queue-wait clock
        # restarts here (decomposition stays truthful across cycles)
        req.enqueue_ts = time.monotonic()
        tracing.trace_event(
            "serve_preempt", rid=req.id, engine=self.engine_id,
            generated_tokens=len(req.generated), freed_blocks=freed,
            preemptions=req.preemptions)
        self._prof_async("e", "decode", req)
        self._sched.push_front(req)
        self._in_transit = None
        tracing.trace_event("serve_requeue", rid=req.id,
                            engine=self.engine_id,
                            queue_depth=len(self._sched.waiting))
        self._prof_async("b", "queue_wait", req)

    def _decode_once(self, events):
        """One batched decode step + the per-iteration token read.

        Watchdog: with ``MXTPU_SERVE_STEP_TIMEOUT`` > 0, an
        iteration whose decode (injection included — serve:step:N:
        hang is the test vector) runs past the budget logs loudly,
        records ``serve_step_overrun`` and dumps the flight recorder
        (``MXTPU_TRACE_DUMP``).  Detection, not interruption: a
        wedged device call cannot be cancelled portably — converting
        the overrun into a post-mortem is this layer's job, killing
        the process is the heartbeat monitor's."""
        import jax
        import jax.numpy as jnp
        B, MB, bs = self.max_batch, self.max_blocks, self.block_size
        slots = self._sched.slots
        running = self._sched.n_running()
        with telemetry.span("serve_decode_prep",
                            running=running) as sp_prep:
            # inside the span: the watchdog below counts an injected
            # hang as part of the step
            resilience.inject("serve", "step")
            tokens = np.zeros(B, np.int32)
            npast = np.zeros(B, np.int32)
            tables = np.zeros((B, MB), np.int32)
            live_blocks = 0
            for i, req in enumerate(slots):
                if req is None:
                    continue
                tokens[i] = req.generated[-1]
                npast[i] = req.n_past
                tables[i, :len(req.block_ids)] = req.block_ids
                # the blocks that hold positions 0 .. n_past: what a
                # read through the table has to touch for this slot
                live_blocks += req.n_past // bs + 1
            tables, npast, tokens = (jnp.asarray(tables),
                                     jnp.asarray(npast),
                                     jnp.asarray(tokens))
        # live over allowed: the share of the allowed context
        # (running x max_blocks, what a gather at max_len reads) that
        # this step's slots hold
        self._m_live_blocks.inc(live_blocks)
        self._m_allowed_blocks.inc(running * MB)
        fn = self._get_step_fn()
        with telemetry.span("serve_decode", running=running,
                            live_blocks=live_blocks) as sp_dec:
            *pools, nxt, logits = fn(
                self._wts, *self._pools, tables, npast, tokens)
            self._pools = tuple(pools)
            # completion barrier (see _admit_one): the token read
            # below already serializes the loop; waiting on the
            # donated pools too keeps the NEXT dispatch off the slow
            # path
            jax.block_until_ready(self._pools[0])
            if nxt.shape[0] > B:
                # a routed model's statistics ride behind the tokens.
                # Read here they are the step's one transfer (the
                # token fetch below finds the host copy made), and
                # the span can say how many experts the step read
                sp_dec.set(experts_touched=self._count_moe(
                    np.asarray(nxt)[B:], decode=True))  # sync-ok: the per-iteration token read
        dt_step = sp_prep.elapsed + sp_dec.elapsed
        if self.step_timeout > 0 and dt_step > self.step_timeout:
            tracing.trace_event(
                "serve_step_overrun", engine=self.engine_id,
                seconds=round(dt_step, 6), budget=self.step_timeout,
                running=running)
            get_logger().warning(
                "serving: decode step took %.3fs against the %.3fs "
                "budget (MXTPU_SERVE_STEP_TIMEOUT); flight-recorder "
                "post-mortem follows when MXTPU_TRACE_DUMP is set",
                dt_step, self.step_timeout)
            tracing.dump_on_fault("serve_step_overrun")
        with telemetry.span("serve_token_fetch"):
            toks = np.asarray(nxt)  # sync-ok: the per-iteration token read
        with telemetry.span("serve_emit") as sp_emit:
            before = len(events)
            for i, req in enumerate(list(slots)):
                if req is None:
                    continue
                req.n_past += 1
                if self.keep_logits:
                    req.logits = logits[i]
                self._append_token(req, int(toks[i]), events)
            sp_emit.set(emitted=len(events) - before)

    def _append_token(self, req, tok, events):
        """Record one emitted token; retire the request when its
        budget or EOS is reached."""
        now = time.monotonic()
        if req.first_token_ts is None:
            req.first_token_ts = now
            self._h_ttft.observe(now - req.submit_ts)
            # serving-side anomaly watchdog: TTFT drift (host floats)
            telemetry.anomaly_watch("serving").observe(
                {"ttft": now - req.submit_ts})
            tracing.trace_event(
                "serve_first_token", rid=req.id,
                engine=self.engine_id,
                ttft_s=round(now - req.submit_ts, 6),
                queue_wait_s=round(req.queue_wait_s, 6),
                prefill_s=round(req.prefill_s, 6))
        else:
            self._h_tok.observe(now - req.last_token_ts)
            telemetry.anomaly_watch("serving").observe(
                {"token_latency": now - req.last_token_ts})
        req.last_token_ts = now
        req.generated.append(tok)
        self._m_tokens.inc()
        events.append((req, tok))
        if (len(req.generated) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)):
            self._retire(req)

    # ----------------------------------------------- terminal paths
    def _close_wait(self, req, now):
        """Close the open queue-wait segment of a QUEUED request
        (observability parity: every terminal path records its wait,
        however it died).  Returns the request's open async phase —
        ``queue_wait`` for queued requests, ``decode`` for running
        ones (admitted requests opened decode at prefill end)."""
        if req.state == QUEUED:
            wait = now - req.enqueue_ts
            req.queue_wait_s += wait
            self._h_wait.observe(wait)
            return "queue_wait"
        return "decode"

    def _release(self, req, now):
        """Shared terminal release: slot cleared and every pool
        block freed in the SAME iteration the terminal was decided,
        so the next admission sees the memory."""
        open_phase = self._close_wait(req, now)
        self._sched.clear(req)
        if req.block_ids:
            self.pool.free(req.block_ids)
        req.block_ids = []
        req.finish_ts = now
        return open_phase

    def _finalize(self, req):
        """Terminal bookkeeping every exit path funnels through:
        exactly one summary, one completed entry, one per-state
        count, and the reap arm-counters released."""
        with self._submit_lock:
            self._live.pop(req.id, None)
            # release only counts cancel() actually took: the
            # stream-abandon flag never bumps the counter, and an
            # uncounted decrement here would steal — and starve —
            # another request's pending cancel behind the reap gate
            if req.cancel_counted and self._cancels_pending > 0:
                self._cancels_pending -= 1
            if (req.ttft_deadline_ts is not None
                    or req.deadline_ts is not None) \
                    and self._deadlines_armed > 0:
                self._deadlines_armed -= 1
            # under the lock: _reject() bumps the same dict from
            # client threads — racing read-modify-writes would
            # silently lose terminal counts
            self._terminal_counts[req.state] = \
                self._terminal_counts.get(req.state, 0) + 1
        self._completed.append(req)
        self._req_summaries.append(self._request_summary(req))

    def _retire(self, req):
        now = time.monotonic()
        self._release(req, now)
        req.state = FINISHED
        tracing.trace_event(
            "serve_retire", rid=req.id, engine=self.engine_id,
            tokens_generated=len(req.generated),
            preemptions=req.preemptions,
            queue_wait_s=round(req.queue_wait_s, 6),
            prefill_s=round(req.prefill_s, 6))
        self._terminal_async(req, "decode")
        self._finalize(req)

    def _fail(self, req, exc):
        """Evict a poisoned or unservable request without touching
        batchmates (queued requests close their wait segment, running
        ones their decode phase).

        Observability parity with retirement: the queue wait is
        recorded (an admission-time eviction would otherwise leave
        the wait histogram blind to the request), a terminal
        ``serve_evict`` event closes the lifecycle, and the flight
        recorder dumps (MXTPU_TRACE_DUMP) — an eviction is a fault,
        and the ring holds the request's whole story."""
        get_logger().warning(
            "serving: evicting request %s after injected/terminal "
            "fault: %s", req.id, exc)
        now = time.monotonic()
        open_phase = self._release(req, now)
        req.state = FAILED
        req.error = exc
        self._m_evict.inc()
        tracing.trace_event(
            "serve_evict", rid=req.id, engine=self.engine_id,
            error=str(exc),
            tokens_generated=len(req.generated),
            queue_wait_s=round(req.queue_wait_s, 6),
            preemptions=req.preemptions)
        self._terminal_async(req, open_phase)
        self._finalize(req)
        tracing.dump_on_fault("serving_eviction")

    def _expire(self, req, why, now):
        """Terminal ``expired``: the request's TTFT or total
        deadline passed.  Partial output is retained on the handle;
        ``req.error`` carries a typed DeadlineExceededError."""
        open_phase = self._release(req, now)
        req.state = EXPIRED
        req.error = resilience.DeadlineExceededError(
            f"serving request {req.id} missed its {why} deadline "
            f"after {len(req.generated)} generated token(s)")
        self._m_expired.inc()
        tracing.trace_event(
            "serve_expire", rid=req.id, engine=self.engine_id,
            why=why, tokens_generated=len(req.generated),
            queue_wait_s=round(req.queue_wait_s, 6),
            preemptions=req.preemptions)
        self._terminal_async(req, open_phase)
        self._finalize(req)

    def _cancel_now(self, req, now):
        """Terminal ``cancelled``: honor a client cancellation.
        Partial output retained; blocks freed this iteration."""
        open_phase = self._release(req, now)
        req.state = CANCELLED
        self._m_cancelled.inc()
        tracing.trace_event(
            "serve_cancel", rid=req.id, engine=self.engine_id,
            tokens_generated=len(req.generated),
            queue_wait_s=round(req.queue_wait_s, 6),
            preemptions=req.preemptions)
        self._terminal_async(req, open_phase)
        self._finalize(req)

    @staticmethod
    def _verdict(req, now):
        """Why a live request must leave the engine now, or None.
        Cancellation wins over expiry (the client already hung up);
        the TTFT deadline only binds before the first token."""
        if req.cancel_requested:
            return "cancel"
        if req.deadline_ts is not None and now >= req.deadline_ts:
            return "total"
        if req.first_token_ts is None \
                and req.ttft_deadline_ts is not None \
                and now >= req.ttft_deadline_ts:
            return "ttft"
        return None

    @staticmethod
    def _next_deadline(req):
        """Earliest future stamp at which ``req`` could expire, or
        +inf.  A stale TTFT stamp after the first token only makes
        the next sweep fire early — the sweep re-verdicts, so early
        is harmless and late is impossible."""
        nxt = float("inf")
        if req.deadline_ts is not None:
            nxt = req.deadline_ts
        if req.first_token_ts is None \
                and req.ttft_deadline_ts is not None:
            nxt = min(nxt, req.ttft_deadline_ts)
        return nxt

    def _reap(self):
        """Honor pending cancellations and blown deadlines — queued
        and running alike — freeing blocks/slots in the same
        iteration.  Two guards keep this off the decode hot path:
        the arm counters (no deadline armed, no cancel pending = one
        integer test) and the earliest-armed-deadline stamp (armed
        but not yet due = one clock read).  Expired/cancelled queued
        requests are REMOVED in place — never pop-all-and-re-push,
        whose empty-queue window a concurrent ``submit()`` admission
        check or a SIGTERM-time ``snapshot()`` would observe."""
        flagged = self._abandon_flagged
        if not (self._cancels_pending or flagged
                or self._deadlines_armed):
            return
        now = time.monotonic()
        if not (self._cancels_pending or flagged) \
                and now < self._deadline_next:
            return
        self._abandon_flagged = False
        with self._submit_lock:
            # reset BEFORE the walk: a submit() arming an earlier
            # deadline mid-sweep mins into this, and the final store
            # below mins back — neither update can be lost
            self._deadline_next = float("inf")
        nxt = float("inf")
        # safe_list: a client thread's submit() may append while we
        # walk (a bare list() of a mutating deque raises); removal
        # serializes against that append under the submit lock
        for req in tracing.safe_list(self._sched.waiting):
            why = self._verdict(req, now)
            if why is None:
                nxt = min(nxt, self._next_deadline(req))
                continue
            with self._submit_lock:
                removed = self._sched.remove_waiting(req)
            if removed:
                if why == "cancel":
                    self._cancel_now(req, now)
                else:
                    self._expire(req, why, now)
        for req in list(self._sched.slots):
            if req is None:
                continue
            why = self._verdict(req, now)
            if why == "cancel":
                self._cancel_now(req, now)
            elif why is not None:
                self._expire(req, why, now)
            else:
                nxt = min(nxt, self._next_deadline(req))
        with self._submit_lock:
            self._deadline_next = min(self._deadline_next, nxt)

    # -------------------------------------------------- observability
    def _prof_async(self, ph, name, req):
        """Emit one chrome-tracing async (b/e) event for a request
        phase when the profiler is running; each request id is an
        async track, placed on a named serving lane.  Lane choice is
        a function of the PHASE, not of ``req.slot`` at emission
        time — slot is nulled by ``Scheduler.clear`` before terminal
        events fire, and every phase of one request must land on one
        lane: ``request``/``queue_wait`` live on the queue lane,
        compute phases (``prefill``/``decode``) on the slot of the
        request's FIRST admission (``last_slot``, pinned in
        ``Scheduler.place`` and never cleared — re-admission into a
        different slot must not split the track)."""
        from .. import profiler
        prof = profiler._profiler
        if not prof.running:
            return
        if name in ("request", "queue_wait") or req.last_slot is None:
            lane = profiler.SERVE_QUEUE_LANE
        else:
            lane = profiler.SERVE_SLOT_LANE0 + req.last_slot
        prof.add_async_event(name,
                             f"req{self.engine_id}.{req.id}", ph,
                             category="serving", lane=lane)

    def _terminal_async(self, req, open_phase):
        """Close a request's open async phases at its terminal
        transition.  ``open_phase`` is the phase still open at that
        point: ``decode`` for retirement (opened at the last
        admission) and for any terminal that catches the request
        RUNNING (expiry, cancellation, the single-runner pool-
        exhaustion failure in ``_grow``); ``queue_wait`` for a
        terminal that catches it QUEUED — ``_close_wait`` decides
        from the request's state."""
        self._prof_async("e", open_phase, req)
        self._prof_async("e", "request", req)

    @staticmethod
    def _request_summary(req):
        """One request's TTFT decomposition for :meth:`stats`."""
        ttft = (req.first_token_ts - req.submit_ts
                if req.first_token_ts is not None else None)
        decode = (req.last_token_ts - req.first_token_ts
                  if req.first_token_ts is not None
                  and req.last_token_ts is not None else None)
        return {
            "id": req.id, "state": req.state,
            "prompt_tokens": len(req.prompt),
            "tokens_generated": len(req.generated),
            "preemptions": req.preemptions,
            "queue_wait_s": round(req.queue_wait_s, 6),
            "prefill_s": round(req.prefill_s, 6),
            "ttft_s": round(ttft, 6) if ttft is not None else None,
            "decode_s": (round(decode, 6)
                         if decode is not None else None),
            "error": (str(req.error)
                      if req.error is not None else None),
        }

    def stats(self):
        """Engine observability snapshot: per-request lifecycle
        summaries (terminal requests from the bounded summary ring,
        live ones in flight), trace/compile counts, and pool state.
        Host-side bookkeeping only — no device access; safe to call
        from a monitoring thread while the engine runs
        (tracing.safe_list absorbs concurrent deque mutation)."""
        live = [self._request_summary(r)
                for r in tracing.safe_list(self._sched.waiting)
                + self._sched.running()]
        return {
            "requests": tracing.safe_list(self._req_summaries),
            "live": live,
            "trace_counts": dict(self.trace_counts),
            "batch_occupancy":
                self._sched.n_running() / self.max_batch,
            "pool_utilization": self.pool.utilization(),
            # what a token leaves in the cache, as the model says
            "cache": {"pools": [dict(c) for c in self.cache_spec],
                      "bytes_per_token": self.token_bytes},
            # SLO/survival view: how every request ended
            # ('rejected' counts submissions shed at the door),
            # plus the admission controller's live pressure
            "terminal_counts": dict(self._terminal_counts),
            "queue_depth": len(self._sched.waiting),
            "queued_tokens": self._sched.queued_tokens,
            "draining": self._draining,
        }
