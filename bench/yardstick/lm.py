"""One run of an LM cell: the program's continuous-batching serve path.

The system under test is ``ServeCell.lm_scheduler``: the weights go
through ``runtime.compile_model`` into a ``ServeCell`` whose
``LMScheduler`` holds ``slots`` decode lanes; each ``step`` joins the
waiting requests (one prefill into a fresh decode state, merged into the
live one), decodes one greedy token on every lane and evicts the lanes
whose request is done.  The loop is closed: after each step the harness
submits one request from the seeded bank for each one that finished, and
it joins at the next step, so every slot is always busy.

Set-up warms every prefill width the mix can reach, decode, the merge and
the eviction, then fills the pool with the bank's first ``slots``
requests.  The window steps the scheduler for ``seconds``.  The check,
after the window and with the program's state freed, teacher-forces the
plain reference over a sample of the requests the window finished, drawn
from the seed with the longest among them, and reads how far below the
reference's best logit each served token's logit lies.

What depends on the model's architecture comes from the module the
configuration names (``cell.arch``, ``bench/arch/<name>.py``): the
weights' layout, the reference, and the work counted from shapes, with
any further terms of its own (``window_work``) that its per-layer
metrics read.  Nothing here names an architecture.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from yardstick import lm_reference, lm_weights, spec
from yardstick.lm_traffic import RequestBank
from yardstick.traffic import rng_for

TRACE_SECONDS = 6.0       # longest traced window: some ten joins
WARM_TOKENS = 2           # tokens each warm-up request is served
# programs of the trace by the name JAX gives them: Engine.prefill and
# Engine.decode_step are jitted lambdas; a join also fills the zeros of a
# fresh decode state and merges it into the live one; every step takes
# the argmax of the logits, and a step that ends requests parks their
# lanes (``where``)
MODEL_PROGRAM = "jit__lambda"
JOIN_PROGRAMS = ("jit_merge_decode_state", "jit_broadcast_in_dim")
STEP_PROGRAMS = ("jit__argmax", "jit__where", "jit_convert_element_type")
UNKNOWN_SHARE = 0.01      # of busy time that programs of no known name may hold


@dataclasses.dataclass
class Log:
    """What the requests were served, step by step.  Steps are numbered
    from the first of set-up; ``window`` is the range the window ran."""

    step_end: list = dataclasses.field(default_factory=list)  # host time
    tokens: dict = dataclasses.field(default_factory=dict)    # rid -> ids
    steps: dict = dataclasses.field(default_factory=dict)     # rid -> steps
    submitted: dict = dataclasses.field(default_factory=dict)  # rid -> time
    done: list = dataclasses.field(default_factory=list)      # rids, in order
    window: range = range(0)
    seconds: float = 0.0
    compiles: int = 0

    def step(self, sched) -> None:
        events = sched.step()
        k = len(self.step_end)
        self.step_end.append(time.perf_counter())
        for ev in events:
            self.tokens.setdefault(ev.rid, []).append(ev.token)
            self.steps.setdefault(ev.rid, []).append(k)
            if ev.done:
                self.done.append(ev.rid)

    def submit(self, sched, rid, prompt, budget) -> None:
        sched.submit(rid, prompt, budget)
        self.submitted[rid] = time.perf_counter()


def program_config(conf: dict, overrides: dict | None = None):
    """The program's configuration for ``conf``: the registry entry's
    full ``config`` with the keys that ``conf['program_overrides']`` names
    set to the file's values, and then ``overrides`` (a control's planted
    fault); every other key of the file's ``model`` block has to agree
    with the entry."""
    from repro.configs import registry
    cfg = registry.get(conf["registry"]).config
    model = conf["model"]
    cfg = cfg.with_(**{k: model[k] for k in conf.get("program_overrides",
                                                     {})})
    for key, want in model.items():
        have = getattr(cfg, key)
        if have != want:
            raise ValueError(f"{conf['registry']}: {key} is {have!r} in the "
                             f"program, {want!r} in the configuration file")
    return cfg.with_(**(overrides or {}))


def _warm_up(sched, bank: RequestBank, log: Log) -> None:
    """One request alone for each prefill width, each served a few
    tokens and evicted: every program the window runs, compiled."""
    for i, n in enumerate(bank.warm_prompt_lengths()):
        log.submit(sched, ("warm", i), bank.tokens[i, :n], WARM_TOKENS)
        while not sched.idle():
            log.step(sched)


def _timed_window(sched, bank, log: Log, next_rid: int, seconds: float,
                  counter, annotate) -> int:
    """Step for ``seconds``; after each step submit one request from the
    bank for each that finished in it.  Returns the next request id."""
    import contextlib

    import jax
    span = jax.profiler.TraceAnnotation if annotate else \
        (lambda _name: contextlib.nullcontext())
    counter.on = True
    first = len(log.step_end)
    t0 = time.perf_counter()
    with span("bench_window"):
        while time.perf_counter() - t0 < seconds:
            n_done = len(log.done)
            with span("lm_step"):
                log.step(sched)
            with span("submit"):
                for _ in range(len(log.done) - n_done):
                    log.submit(sched, next_rid, *bank.request(next_rid))
                    next_rid += 1
    log.seconds = time.perf_counter() - t0
    counter.on = False
    log.compiles = counter.count
    log.window = range(first, len(log.step_end))
    return next_rid


def _latencies(log: Log, t0: float):
    """Token events in the window; time to first token of each request
    submitted in the window whose first token came in it; the gaps
    between consecutive tokens of a request, both in the window."""
    w = log.window
    tokens, ttft, itl = 0, [], []
    for rid, steps in log.steps.items():
        inside = [k for k in steps if k in w]
        tokens += len(inside)
        sub = log.submitted.get(rid)
        if sub is not None and sub >= t0 and steps[0] in w:
            ttft.append(log.step_end[steps[0]] - sub)
        itl += [log.step_end[b] - log.step_end[a]
                for a, b in zip(inside, inside[1:])]
    return tokens, np.asarray(ttft), np.asarray(itl)


def _bad_requests(log: Log, bank: RequestBank, rids, vocab: int) -> int:
    """Requests among ``rids`` that finished with another number of tokens
    than their budget, or with a token outside the vocabulary."""
    bad, done = 0, set(log.done)
    for rid in rids:
        toks = np.asarray(log.tokens.get(rid, ()))
        if rid in done and (len(toks) != bank.request(rid)[1]
                            or np.any(toks < 0) or np.any(toks >= vocab)):
            bad += 1
    return bad


def _window_work(arch, log: Log, bank: RequestBank, model: dict,
                 num: dict):
    """Work of the window's steps, counted from shapes by the architecture
    module ``arch``: the real tokens' matmul operations (prompts
    prefilled at their own lengths, tokens decoded at their lanes'
    depths), and decode's least time terms."""
    act = _dtype(num["activations"]).itemsize
    wbytes = _dtype(num["weights"]).itemsize
    nbytes = _dtype(num["norm_scales"]).itemsize
    depths = {k: [] for k in log.window}
    prefill_flops = 0
    for rid, steps in log.steps.items():
        if isinstance(rid, tuple):          # warm-up requests
            continue
        n = len(bank.request(rid)[0])
        for j, k in enumerate(steps):
            if k in depths:
                depths[k].append(n - 1 + j)
        if steps[0] in depths and n > 1:
            prefill_flops += arch.tokens_flops(model, 0, n - 1, head=False)
    decode_flops = [sum(arch.tokens_flops(model, p, 1, head=True)
                        for p in ds) for ds in depths.values()]
    decode_bytes = [arch.decode_min_bytes(model, ds, act, wbytes, nbytes)
                    for ds in depths.values()]
    return prefill_flops, decode_flops, decode_bytes


def _dtype(name: str):
    import ml_dtypes
    return np.dtype(getattr(ml_dtypes, name, name))


def _sample(log: Log, bank: RequestBank, seed: int, n: int) -> list:
    """``n`` requests the window finished, drawn from the seed, and the
    longest of them (prompt and output together)."""
    done = [rid for rid in log.done if not isinstance(rid, tuple)
            and log.steps[rid][-1] in log.window]
    total = [len(bank.request(r)[0]) + len(log.tokens[r]) for r in done]
    longest = done[int(np.argmax(total))]
    rest = [r for r in done if r != longest]
    pick = rng_for(seed, 14).choice(len(rest), size=min(n, len(rest)),
                                    replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _teacher_rows(log: Log, bank: RequestBank, rids: list, length: int):
    """Rows of prompt + served tokens (the last served token is no input),
    padded to ``length``; the served tokens at the positions that produced
    them; and which positions those are."""
    tokens = np.zeros((len(rids), length), np.int32)
    served = np.zeros((len(rids), length), np.int32)
    mask = np.zeros((len(rids), length), bool)
    for i, rid in enumerate(rids):
        prompt, out = bank.request(rid)[0], np.asarray(log.tokens[rid])
        seq = np.concatenate([prompt, out[:-1]])
        tokens[i, :len(seq)] = seq
        at = len(prompt) - 1 + np.arange(len(out))
        served[i, at], mask[i, at] = out, True
    return tokens, served, mask


def _gaps(arch, w, conf, tokens, served, mask, rows: int, lower: bool):
    """The reference's best logit less its logit of each served token, at
    the served positions, computed ``rows`` requests at a time by the
    architecture module ``arch``; with ``lower`` also the same for the
    token the control puts first."""
    model, stated = conf["model"], conf["numerics"]["weights"]
    num = lm_reference.Numerics.stated(conf["numerics"])
    served_gap, control_gap = [], []
    for b in range(0, len(tokens), rows):
        sl = slice(b, b + rows)
        tok, srv = tokens[sl], served[sl]
        pad = rows - len(tok)
        if pad:
            tok = np.concatenate([tok, np.repeat(tok[:1], pad, 0)])
            srv = np.concatenate([srv, np.repeat(srv[:1], pad, 0)])
        targets = [srv]
        if lower:
            _, low_top, _ = arch.evaluate(
                w, tok, srv[None], model, num.lowered(), stated)
            targets.append(low_top)
        best, _, picked = arch.evaluate(
            w, tok, np.stack(targets), model, num, stated)
        m = mask[sl]
        k = len(m)
        served_gap.append((best[:k] - picked[0, :k])[m])
        if lower:
            control_gap.append((best[:k] - picked[1, :k])[m])
    return (np.concatenate(served_gap),
            np.concatenate(control_gap) if lower else None)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *, dev,
        count: int, slots: int | None = None, control: bool = False,
        program_overrides: dict | None = None, record: dict | None = None,
        t_start: float, log=print) -> tuple[dict, dict]:
    """One run of the LM cell ``cell``; the arguments are
    :func:`yardstick.harness.run`'s (``slots`` overrides the mix's slot
    count; ``program_overrides`` sets keys of the program's configuration
    apart from what the file states, a planted fault)."""
    import jax
    from repro import cell as cellmod
    from repro import runtime
    from repro.launch import steps as program_steps
    from yardstick import harness

    conf, mix, arch = cell.config, cell.traffic, cell.arch
    model = conf["model"]
    counter = harness._CompileCounter()
    rec = Log()
    try:
        cfg = program_config(conf, program_overrides)
        t_init = time.perf_counter()
        params = lm_weights.make(seed, arch.shapes(model), conf["numerics"],
                                 conf["init_std"])
        lm_weights.check_layout(params, jax.eval_shape(
            lambda k: program_steps.model_module(cfg).init_params(cfg, k),
            jax.random.PRNGKey(0)))
        eng = runtime.compile_model(cfg, params, backend=conf["backend"])
        jax.block_until_ready(params)
        t_model = time.perf_counter()
        bank = RequestBank(mix, seed, model["vocab_size"])
        n_slots = int(slots or bank.slots)
        t_bank = time.perf_counter()
        with cellmod.ServeCell(eng, slots=n_slots) as serve:
            sched = serve.lm_scheduler(max_len=bank.max_len, eos_id=None)
            _warm_up(sched, bank, rec)
            t_warm = time.perf_counter()
            warm_done = len(rec.done)
            for rid in range(n_slots):
                rec.submit(sched, rid, *bank.request(rid))
            rec.step(sched)
            # a request under way may end in this first step; the loop is
            # closed, so it is replaced at once, as in the window
            next_rid = n_slots
            for _ in range(len(rec.done) - warm_done):
                rec.submit(sched, next_rid, *bank.request(next_rid))
                next_rid += 1
            setup_s = time.perf_counter() - t_start
            log(f"set-up {setup_s:.3f} s: start and chip "
                f"{t_init - t_start:.3f} s, weights and compile_model "
                f"{t_model - t_init:.3f} s, request bank "
                f"{t_bank - t_model:.3f} s, warm-up of "
                f"{len(bank.warm_prompt_lengths())} prefill widths "
                f"{t_warm - t_bank:.3f} s, first join of {n_slots} slots "
                f"{time.perf_counter() - t_warm:.3f} s")
            before = harness.counters()
            t0 = time.perf_counter()
            with harness.profiled(trace) as trace_dir:
                next_rid = _timed_window(
                    sched, bank, rec, next_rid,
                    min(seconds, TRACE_SECONDS) if trace else seconds,
                    counter, trace)
            counted = harness.counter_increase(before, harness.counters())
            stats = dev.memory_stats() or {}
            mem_peak = int(stats.get("peak_bytes_in_use", 0))
            del sched
        del eng, serve
    finally:
        counter.close()

    tokens, ttft, itl = _latencies(rec, t0)
    in_window = [rid for rid in rec.submitted if not isinstance(rid, tuple)
                 and (rec.submitted[rid] >= t0
                      or rec.steps.get(rid, [-1])[-1] in rec.window)]
    bad = _bad_requests(rec, bank, in_window, model["vocab_size"])
    steps_ms = 1e3 * np.diff([rec.step_end[rec.window[0] - 1]]
                             + [rec.step_end[k] for k in rec.window])
    log(f"window {rec.seconds:.3f} s: {len(rec.window)} steps of {n_slots} "
        f"slots, {tokens} tokens, {next_rid - n_slots} requests submitted; "
        f"step p50 {np.median(steps_ms):.3f} ms max {steps_ms.max():.3f} "
        f"ms; {len(ttft)} first tokens, {len(itl)} token gaps; memory "
        f"peak {mem_peak} B")

    # -- the check, after the window and with the program's state freed --
    t_ref = time.perf_counter()
    rids = _sample(rec, bank, seed, int(conf["check"]["requests"]))
    tok, served, mask = _teacher_rows(rec, bank, rids, bank.max_len)
    gap, low_gap = _gaps(arch, params, conf, tok, served, mask,
                         int(conf["check"]["rows"]), control)
    log(f"reference over {len(rids)} requests, {int(mask.sum())} served "
        f"tokens, in {time.perf_counter() - t_ref:.3f} s; served tokens "
        f"that are not the reference's first: "
        f"{int(np.sum(gap > 0))}")
    limits = conf["check"]["limits"]
    checks = {"token_gap": {"value": float(gap.max()),
                            "limit": limits["token_gap"]},
              "window_compiles": {"value": rec.compiles, "limit": 0},
              "bad_requests": {"value": bad, "limit": 0}}
    if record is not None:
        record.update(rids=np.array(rids), tokens=tok, served=served,
                      mask=mask, gap=gap,
                      **({"control.weights": low_gap} if control else {}))
    red = harness.reduce_trace(trace_dir) if trace else None
    ctx = reader_context(cell, rec, bank, red, dev.device_kind, counted,
                         log) if trace else None
    result = harness.result_line(
        cell, dev, count, mem_peak, checks, attempted=len(in_window),
        failed=bad,
        values=None if trace else {
            "setup_s": setup_s,
            "tokens_per_s": tokens / rec.seconds,
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "itl_p95_ms": 1e3 * float(np.percentile(itl, 95)),
        },
        red=red, ctx=ctx,
        control={"weights": {"token_gap": float(low_gap.max())}}
        if control else None)
    return result, checks


class Ambiguous(ValueError):
    """The traced programs do not fit the phases of an LM step."""


def phases(red, steps: int, join_steps: int) -> dict:
    """Device seconds of the traced window's programs by their part in a
    step, told apart by the names JAX gives them.

    Of the jitted lambdas (``MODEL_PROGRAM``), decode is the one that runs
    on every step, give or take one at the window's edges; the others are
    the prefills, one for each width, which run once on each join step,
    so that their runs add up to ``join_steps``.  ``join`` is the device
    time of the prefills and of ``JOIN_PROGRAMS``.  Also how many times
    decode ran.

    Raises :class:`Ambiguous` where the trace does not fit that: fewer
    than three steps; a join on (nearly) every step; no lambda, or more
    than one, that runs every step; prefill runs that do not add up to
    the join steps; or programs of no known name that hold more than
    ``UNKNOWN_SHARE`` of the busy time.  Each means that the program's
    step is no longer built as it was, and a share read from the run
    counts alone could be of the wrong programs."""
    if steps < 3 or join_steps >= steps - 1:
        raise Ambiguous(f"{join_steps} join steps among {steps}: decode and "
                        "the joins cannot be told apart by their runs")
    runs, secs = red.module_runs, red.module_s
    family = {n: n.split("(", 1)[0] for n in runs}
    lambdas = [n for n in runs if family[n] == MODEL_PROGRAM]
    every = [n for n in lambdas if runs[n] >= steps - 1]
    if len(every) != 1:
        raise Ambiguous(f"{len(every)} jitted lambdas run on every one of "
                        f"{steps} steps; decode has to be one")
    decode = every[0]
    prefills = [n for n in lambdas if n != decode]
    prefill_runs = sum(runs[n] for n in prefills)
    if abs(prefill_runs - join_steps) > 1:
        raise Ambiguous(f"the prefills ran {prefill_runs:g} times on "
                        f"{join_steps} join steps")
    unknown = sum(secs.get(n, 0.0) for n in runs
                  if family[n] not in (MODEL_PROGRAM,) + JOIN_PROGRAMS
                  + STEP_PROGRAMS)
    if unknown > UNKNOWN_SHARE * red.busy_s:
        raise Ambiguous(f"programs of no known name hold {unknown:.6f} s of "
                        f"{red.busy_s:.6f} s busy")
    join = sum(secs.get(n, 0.0) for n in runs
               if n in prefills or family[n] in JOIN_PROGRAMS)
    return {"decode": secs.get(decode, 0.0), "decode_runs": runs[decode],
            "join": join}


def reader_context(cell, rec: Log, bank: RequestBank, red, kind: str,
                   counters: dict, log=print) -> dict:
    """What the per-layer readers of a traced LM run read: the trace's
    reduction (``trace``), the phases of the step, ``counters`` (the
    increase of each program counter over the window) and under ``work``
    the window's work counted from shapes, with the architecture's own
    terms (``window_work``) beside the harness's."""
    from yardstick import peaks
    conf, arch = cell.config, cell.arch
    model, num = conf["model"], conf["numerics"]
    chip = peaks.peaks(kind)
    prefill_flops, decode_flops, decode_bytes = _window_work(
        arch, rec, bank, model, num)
    work = {"model_flops": prefill_flops + sum(decode_flops),
            "decode_flops": decode_flops, "decode_min_bytes": decode_bytes}
    if hasattr(arch, "window_work"):
        own = arch.window_work(rec, bank, model, num)
        clash = sorted(set(own) & set(work))
        if clash:
            raise ValueError(f"window_work's terms {clash} are the "
                             "harness's own")
        work.update(own)
    joins = {rec.steps[r][0] for r in rec.steps
             if not isinstance(r, tuple) and rec.steps[r][0] in rec.window}
    try:
        ph = phases(red, len(rec.window), len(joins))
    except Ambiguous as e:
        log(f"phases of the LM step not read, so neither are the metrics "
            f"that need them: {e}")
        ph = None
    return {
        "cell": cell.name, "steps": len(rec.window), "trace": red,
        "phases": ph, "counters": counters, "work": work,
        "peak_flops_per_s": chip[num["peak"]],
        "hbm_bytes_per_s": chip["hbm_bytes_per_s"],
    }
