"""Driver of a ``serve`` cell: the program's ``ServingEngine`` under
the load of traffic.py, every event timed on the benchmark's clock.

One loop over ``engine.step()`` (the engine is single-threaded, as
``chip_smoke.phase_serve`` drives it).  A token's time is the moment
the step that produced it returned.  The load starts ``ramp_seconds``
before the window opens (set-up: the callers of a closed loop start
together, and the engine takes all their prompts in within one step,
which is no steady state).  The window closes at ``--seconds``: no
request is sent after that, the requests in flight are served to their
end (the first tokens of those sent inside the window count for the
tail of time to first token, later tokens for nothing), and the rate
is all tokens that came out inside the window over the window.

After the window: peak memory is read, the engine and the model are
freed, and the reference (reference/transformer.py, in the precision
the configuration states for serving, ``serve.reference_precision``)
is run once over the prompt and the served tokens of the requests
that finished (``checked_requests`` at the most, the longest among
them): ``gaps`` says what is compared.
"""
import gc
import os
import time

import numpy as np

from . import trace, weights
from .reduce_trace import WINDOW_SPAN
from .traffic import Plan, prefill_buckets
from .train import memory_peak, settled_block

PAD_TO = 512          # the reference's sequence lengths are whole
#                       multiples of this: few shapes to compile


def build(h, cell, seed, mx):
    """The model with seeded weights in its Parameters, of the dtype
    that the configuration states (``serve.weights_dtype``), and an
    engine on it.  A family that does not count its own work is
    turned away here, before set-up is paid."""
    cfg, traffic = cell.config, cell.traffic
    fam = h.family(cfg)
    lacking = [f for f in ("prefill_flops", "decode_flops")
               if not hasattr(fam, f)]
    if lacking:
        raise AttributeError(
            f"family {cfg['family']!r} is served by {cell.name} but "
            f"has no {' and no '.join(lacking)}(cfg, n): a served "
            "family counts its own work (benchmark/README.md)")
    block = settled_block(fam, mx, cfg, mx.tpu(0),
                          fam.param_shapes(cfg), seed, trained=False,
                          dtype=cfg["serve"]["weights_dtype"])
    eng = mx.serving.ServingEngine(block, **traffic["engine"])
    return fam, block, eng


def warm(eng, traffic, vocab, seed):
    """One request through each prefill bucket the mix reaches, and
    through the decode step."""
    rs = np.random.RandomState((seed + 7) % (2 ** 32))
    top = traffic["prompt_len"]["max"]
    for bucket in prefill_buckets(traffic, eng.block_size,
                                  eng.model._max_len):
        eng.submit(rs.randint(0, vocab, min(bucket, top)), 2)
    while eng.has_work():
        eng.step()
    eng.take_completed()


class Drive:
    """The load loop and its records."""

    def __init__(self, eng, plan, clock=time.perf_counter):
        self.eng, self.plan, self.clock = eng, plan, clock
        self.records = {}         # request id -> record
        self.step_s = []          # seconds of each step that gave tokens
        self.t0 = self.t_close = None

    def _submit(self, due):
        import jax.profiler as prof
        toks, new = self.plan.next()
        with prof.TraceAnnotation("bench.submit"):
            req = self.eng.submit(toks, new)
        self.records[req.id] = {"req": req, "due": due, "prompt": toks,
                                "times": [], "tokens": []}
        return req

    def run(self, seconds, tick=None, ramp=0.0):
        """Load for ``ramp`` seconds (set-up: the callers start
        together, and their first prompts are taken in one after the
        other in one step, which is no steady state), then the window
        of ``seconds``, then serve what is in flight."""
        import jax.profiler as prof
        clock = self.clock
        begin = clock()
        for _ in range(self.plan.clients):
            self._submit(begin)
        while self.eng.has_work():
            now = clock()
            if self.t0 is None and now - begin >= ramp:
                self.t0 = now
            if self.t0 is not None:
                if tick:
                    tick(now - self.t0)
                if self.t_close is None and now - self.t0 >= seconds:
                    self.t_close = now
            with prof.TraceAnnotation("bench.engine_step"):
                ts = clock()
                events = self.eng.step()
                te = clock()
            if events and self.t0 is not None and self.t_close is None:
                self.step_s.append(te - ts)
            for req, tok in events:
                rec = self.records[req.id]
                rec["times"].append(te)
                rec["tokens"].append(int(tok))
                if req.done and self.t_close is None:
                    self._submit(te)
        return self

    # ------------------------------------------------------ metrics
    def in_window(self, t):
        return self.t0 < t <= self.t_close

    def end_to_end(self):
        window = self.t_close - self.t0
        recs = list(self.records.values())
        tokens = sum(1 for r in recs for t in r["times"]
                     if self.in_window(t))
        ttft = [r["times"][0] - r["due"] for r in recs
                if r["times"] and r["due"] >= self.t0]
        gaps = [b - a for r in recs
                for a, b in zip(r["times"], r["times"][1:])
                if self.in_window(b)]
        return {"serve_tok_per_s": tokens / window,
                "_ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
                "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
                "_ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
                "_itl_p50_ms": 1e3 * float(np.percentile(gaps, 50)),
                "_requests": len(recs), "_first_tokens": len(ttft),
                "_tokens": tokens,
                "_window_s": window}

    def counts(self):
        recs = list(self.records.values())
        failed = sum(1 for r in recs if r["req"].state != "finished"
                     or len(r["tokens"]) != r["req"].max_new_tokens)
        return len(recs), failed

    def flops_between(self, fam, cfg, lo, hi):
        """Required operations, as the family counts them, of every
        prompt taken in and token generated with its time in
        [lo, hi]."""
        total = 0
        for r in self.records.values():
            plen = len(r["prompt"])
            for i, t in enumerate(r["times"]):
                if lo <= t <= hi:
                    total += fam.prefill_flops(cfg, plen) if i == 0 \
                        else fam.decode_flops(cfg, plen + i)
        return total

    def sample(self, k, seed):
        """``k`` finished requests, the longest among them, the rest
        drawn from the seed: (prompt, served tokens) each."""
        done = [r for r in self.records.values()
                if r["req"].state == "finished" and r["tokens"]]
        done.sort(key=lambda r: -(len(r["prompt"]) + len(r["tokens"])))
        rs = np.random.RandomState((seed + 11) % (2 ** 32))
        rest = [done[i] for i in rs.permutation(len(done) - 1)[:k - 1]
                + 1] if len(done) > 1 else []
        return [(np.asarray(r["prompt"], np.int32),
                 np.asarray(r["tokens"], np.int32))
                for r in done[:1] + rest]


def far_gap_share(gap, margin, error, sigmas, least_flips):
    """(share, where): the gaps of ``gap`` wider than ``sigmas`` times
    the control's noise, summed, as a share of what the control is
    expected to lose at such margins in this text.

    ``margin``  the reference's best logit less its second, at every
                position compared;
    ``error``   the control's error on that margin there.  Its root
                mean square is the control's noise, sigma: every
                position tells of it, not only the near-ties.

    The control turns a margin m where its error is under -m.  The
    share of all positions' errors that are, times m, is what it is
    expected to lose there; the yardstick is the sum of that over the
    margins wider than ``sigmas * sigma``.  A program half as noisy
    seldom turns such a margin (its own 1.5 sigmas and more), so the
    two read five times apart; the mean of all gaps read them three
    times apart on no dozen seeds.  Where the text gives the control
    little to turn (a model that repeats itself under wide margins),
    no tokens tell the two apart and a ratio of a few flips is noise:
    the yardstick is never under ``least_flips`` turns of the least
    width that counts.  PERF.md, section 6, has the readings."""
    gap, margin, error = (np.asarray(v, np.float64)
                          for v in (gap, margin, error))
    sigma = float(np.sqrt(np.mean(error ** 2)))
    far = sigmas * sigma
    turned = np.searchsorted(np.sort(error), -margin,
                             side="right") / len(error)
    keep = margin > far
    yard = float((margin * turned)[keep].sum())
    flips = float(turned[keep].sum())
    share = float(gap[gap > far].sum()) / max(yard, least_flips * far,
                                              1e-30)
    return share, (f"{int((gap > far).sum())} gaps beyond {far:.3g}; "
                   f"the control's yardstick {yard:.3g} over "
                   f"{flips:.3g} turns, at the least "
                   f"{least_flips * far:.3g}")


def gaps(fam, cfg, seed, sample, of_control=False):
    """What ``correct`` compares in a serve cell, as name -> (number,
    where).  Over every served token of the sample, how far its logit
    lies below the best of the reference (reference/transformer.py in
    ``serve.reference_precision``), run once over prompt and served
    tokens.  Beside it the control's yardstick, from the reference in
    ``serve.control_precision`` at the same positions.

    ``far_gap_share``  the served tokens' gaps beyond
                   ``serve.far_gap_sigmas`` of the control's noise, as
                   a share of the control's expected loss at such
                   margins (``far_gap_share`` above).
    ``token_gap``  the widest gap of a served token (shown, not
                   compared: no limit separates it, PERF.md).

    ``of_control`` puts the control in the program's place: the
    tokens it puts first are read as if they had been served."""
    import jax
    import jax.numpy as jnp
    params = weights.make(fam.param_shapes(cfg), seed,
                          cfg["serve"]["weights_dtype"])
    stated = cfg["serve"]["reference_precision"]
    control = cfg["serve"]["control_precision"]

    def below_best(params, toks):
        lg = fam.reference_logits(params, toks[None], cfg, stated)[0]
        low = fam.reference_logits(params, toks[None], cfg, control)[0]
        top, at = jax.lax.top_k(lg, 2)
        low_top = jnp.take_along_axis(low, at, axis=-1)
        margin = top[:, 0] - top[:, 1]

        def gap(chosen):
            return top[:, 0] - jnp.take_along_axis(
                lg, chosen[:, None], axis=-1)[:, 0]
        # position i chooses the token at i + 1
        return (gap(jnp.roll(toks, -1)), gap(jnp.argmax(low, axis=-1)),
                margin, low_top[:, 0] - low_top[:, 1] - margin)

    fn = jax.jit(below_best)
    rows = []
    for prompt, tokens in sample:
        n = len(prompt) + len(tokens)
        padded = np.zeros(min(-(-n // PAD_TO) * PAD_TO,
                              cfg["max_position_embeddings"]), np.int32)
        padded[:len(prompt)] = prompt
        padded[len(prompt):n] = tokens
        rows.append([np.asarray(v)[len(prompt) - 1:n - 1]
                     for v in fn(params, padded)])
    mine, theirs, margin, error = (np.concatenate(v)
                                   for v in zip(*rows))
    served = theirs if of_control else mine
    share, where = far_gap_share(
        served, margin, error, cfg["serve"]["far_gap_sigmas"],
        cfg["serve"]["yardstick_flips"])
    of = f"{len(served)} tokens of {len(sample)} requests"
    return {"far_gap_share": (share, f"{of}; {where}"),
            "token_gap": (float(served.max()), of)}


def run(h, cell, args, t_start, dev, mx):
    import jax
    cfg, traffic = cell.config, cell.traffic
    fam, block, eng = build(h, cell, args.seed, mx)
    built_s = time.perf_counter() - t_start
    warm(eng, traffic, cfg["vocab_size"], args.seed)
    warm_s = time.perf_counter() - t_start - built_s

    drive = Drive(eng, Plan(traffic, cfg["vocab_size"], args.seed))
    ramp = traffic.get("ramp_seconds", 0.0)
    traced, tick, state = None, None, {"rec": None, "t": None}
    if args.trace:
        span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        length = min(traffic["trace_seconds"], args.seconds)

        def tick(elapsed):
            if state["rec"] is None:              # the window opens
                state["rec"] = trace.Recording().start()
                span.__enter__()
            elif state["t"] is None and elapsed >= length:
                span.__exit__(None, None, None)
                # read before the profiler is stopped: stopping takes
                # a while in which no step runs, and the work counted
                # is that of [t - the span's length, t]
                state["t"] = drive.clock()
                state["rec"].stop()
    drive.run(args.seconds, tick, ramp)
    setup_s = drive.t0 - t_start
    if args.trace:
        tick(float("inf"))
        traced = state["rec"].reduce(
            keep_as=os.environ.get("BENCH_KEEP_TRACE"),
            required=not args.rehearse)
    peak, memory = memory_peak(dev)
    e2e = drive.end_to_end()
    print({"memory": memory, "setup_built_s": built_s, "setup_warm_s": warm_s,
           "setup_ramp_s": setup_s - built_s - warm_s,
           **{k: v for k, v in e2e.items() if k.startswith("_")},
           "engine_steps": len(drive.step_s)}, flush=True)
    attempted, failed = drive.counts()
    sample = drive.sample(traffic["checked_requests"], args.seed)
    ctx = {"trace": traced, "config": cfg, "traffic": traffic,
           "run_values": e2e,
           "spans": {"bench.engine_step": drive.step_s}}
    if args.trace:
        # a rehearsal's trace has no device plane and so no window of
        # its own: the family's count is taken all the same, over the
        # seconds that were recorded
        window = traced["window_s"] if traced else length
        ctx["flops_in_trace"] = drive.flops_between(
            fam, cfg, state["t"] - window, state["t"])
    if traced:
        traced["steps"] = len(traced["spans"].get(
            "bench.engine_step", []))
        ctx["spans"] = traced["spans"]

    # free the engine and the model before the reference takes the chip
    drive.eng = drive.records = None
    del eng, block, drive
    gc.collect()      # the engine and its jitted closures are a cycle
    return {"attempted": attempted, "failed": failed,
            "numbers": gaps(fam, cfg, args.seed, sample),
            "end_to_end": {"setup_s": setup_s, **{
                k: v for k, v in e2e.items() if not k.startswith("_")}},
            "memory_peak_bytes": peak, "ctx": ctx}


def short_load(h, cell, seed, mx, seconds):
    """A short window at the cell's own load on a fresh engine: the
    sample to compare and the drive's own figures.  The engine, the
    model and the records die with this frame."""
    cfg, traffic = cell.config, cell.traffic
    _, _, eng = build(h, cell, seed, mx)
    warm(eng, traffic, cfg["vocab_size"], seed)
    drive = Drive(eng, Plan(traffic, cfg["vocab_size"], seed))
    drive.run(seconds, ramp=traffic.get("ramp_seconds", 0.0))
    return (drive.sample(traffic["checked_requests"], seed),
            drive.end_to_end(), drive.counts())


def calibrate(h, cell, seeds, n_controls, emit, dev, mx, seconds=8.0):
    """Readings for limits/<cell>.json: per seed the program's numbers
    after a short window at the cell's own load; for the first
    ``n_controls`` seeds also the control's, put in the program's
    place.  Every row goes through ``correct.verdict``."""
    from . import correct
    cfg = cell.config
    fam = h.family(cfg)
    control = cfg["serve"]["control_precision"]
    samples = {}
    for seed in seeds:
        samples[seed], e2e, counts = short_load(h, cell, seed, mx,
                                                seconds)
        gc.collect()    # the engine and its jitted closures: a cycle
        emit({"seed": seed, "who": "load", "counts": counts, **e2e,
              "memory": memory_peak(dev)[1]})

    for i, seed in enumerate(seeds):
        emit({"seed": seed, "who": "program", **correct.judged(
            gaps(fam, cfg, seed, samples[seed]), cell.limits)})
        if i < n_controls:
            emit({"seed": seed, "who": f"control_{control}",
                  **correct.judged(gaps(fam, cfg, seed, samples[seed],
                                        of_control=True),
                                   cell.limits)})
