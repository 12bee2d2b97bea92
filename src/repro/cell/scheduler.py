"""Continuous batching for LM serving: in-flight join, per-lane evict.

``launch/serve.py``'s slot loop refilled by re-running ``prefill`` over
the WHOLE batch from a re-initialised decode state — a global drain
barrier that also wiped resident lanes' KV caches mid-request.  The
scheduler replaces it with true continuous batching:

* the decode state's ``index`` is a per-lane [B] vector
  (``models.transformer``: cache writes scatter at ``[lane, idx[lane]]``,
  RoPE positions and validity bounds are per-lane), so every lane decodes
  at its own depth;
* joiners prefill straight into the live decode state, one program a
  join step (``Engine.join``): each joiner's right-padded prompt minus
  its last token is prefilled ALONE, at batch 1 into a fresh cache of
  the join's width, and written into its lane's slots ``0..w``
  (``transformer.merge_decode_state``), in place since the live state
  is donated.  No pad row is computed, resident lanes never stop
  decoding and their caches are untouched; slots past ``w`` keep the
  lane's previous request's keys, which the per-lane validity bound
  masks as it masks pad keys.  On moe a joiner's routing is its own,
  never shared with its co-joiners';
* the first ``decode_step`` after a join feeds the prompt's LAST token,
  writing its KV at slot ``len-1`` under the lane's own position — from
  then on the lane is indistinguishable from one that prefilled alone.

Because positions, cache slots and validity masks are all per-lane, a
request's greedy token sequence depends only on its prompt, the batch
width and the prefill pad width — NOT on what the other lanes are doing.
With a fixed ``prefill_len`` the schedule is invisible to outputs:
submitting the same requests in any order yields bit-identical tokens
per request (tests/test_cell.py).

Families: dense / moe (KV-cache attention, where pad keys can be masked
after the fact).  Recurrences (rwkv, hybrid's ring+SSM) fold pad tokens
irreversibly into their state under any batched padding and keep the
drain-batch serve path.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request: prompt tokens + a generation budget."""

    rid: Any
    prompt: np.ndarray          # [L] int32, L >= 1
    max_new: int                # generation budget (tokens)


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One decoded token for one request (``done`` on the last one)."""

    rid: Any
    token: int
    done: bool = False
    reason: str = ""            # "eos" | "len" when done


def _bucket(n: int) -> int:
    """Next power of two >= n: bounds prefill retraces to O(log max_len)."""
    b = 1
    while b < n:
        b *= 2
    return b


class LMScheduler:
    """A fixed pool of ``slots`` decode lanes with in-flight join/evict.

    Drive with ``submit`` + repeated ``step``; each ``step`` joins
    waiting requests into free lanes (one join program, no drain),
    advances EVERY lane one greedy token, and evicts lanes whose request
    hit EOS or its budget.  Evicted lanes keep decoding garbage
    until re-joined (the batch shape is static); their outputs are
    discarded and their per-lane index is parked at 0 so cache scatters
    stay in bounds.

    ``engine`` is a ``runtime.Engine`` or a swap-safe
    ``runtime.EngineHandle`` — the scheduler reads the live engine each
    step, so a hot-swap between steps changes params only (lane caches
    and positions survive; exec-config compatibility is enforced by
    ``EngineHandle.swap``).
    """

    def __init__(self, engine, *, slots: int, max_len: int,
                 eos_id: Optional[int] = None,
                 prefill_len: Optional[int] = None, metrics=None):
        cfg = self._engine(engine).exec_cfg
        assert cfg.family in ("dense", "moe"), \
            f"continuous batching covers dense/moe, not {cfg.family}"
        self._eng_ref = engine
        self.slots, self.max_len, self.eos_id = slots, max_len, eos_id
        self.prefill_len = prefill_len      # None -> per-group pow2 bucket
        self.metrics = metrics
        self.queue: list[Request] = []
        self.active: list[Optional[Request]] = [None] * slots
        self._remaining = np.zeros(slots, np.int64)
        self.state = self._engine(engine).init_decode_state(slots, max_len)
        # per-lane depth from step one (the join program's carry is [B])
        self.state["index"] = jnp.zeros((slots,), jnp.int32)
        self._cur = jnp.zeros((slots,), jnp.int32)

    @staticmethod
    def _engine(ref):
        return ref.engine if hasattr(ref, "engine") else ref

    @property
    def engine(self):
        return self._engine(self._eng_ref)

    # -- request intake ----------------------------------------------------

    def submit(self, rid, prompt, max_new: int) -> None:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        assert 1 <= prompt.size and prompt.size - 1 + max_new <= self.max_len, \
            (prompt.size, max_new, self.max_len)
        self.queue.append(Request(rid, prompt, int(max_new)))
        if self.metrics is not None:
            self.metrics.queue_depth.set(len(self.queue))

    @property
    def n_active(self) -> int:
        return sum(1 for r in self.active if r is not None)

    def idle(self) -> bool:
        return self.n_active == 0 and not self.queue

    # -- one scheduler tick ------------------------------------------------

    def step(self) -> list[TokenEvent]:
        """Join waiting requests, decode one token on every lane, evict."""
        if self.idle():
            return []
        self._join()
        eng, met = self.engine, self.metrics
        t0 = time.perf_counter()
        logits, self.state = eng.decode_step(self._cur, self.state)
        self._cur = jnp.argmax(logits, -1).astype(jnp.int32)
        toks = np.asarray(self._cur)
        if met is not None:
            met.decode_ms.observe(1e3 * (time.perf_counter() - t0))
            met.tokens.inc(self.n_active)
        events, evicted = [], []
        for i, req in enumerate(self.active):
            if req is None:
                continue
            self._remaining[i] -= 1
            is_eos = self.eos_id is not None and int(toks[i]) == self.eos_id
            done = is_eos or self._remaining[i] <= 0
            events.append(TokenEvent(req.rid, int(toks[i]), done,
                                     ("eos" if is_eos else "len")
                                     if done else ""))
            if done:
                self.active[i] = None
                evicted.append(i)
        if evicted:
            # park freed lanes at depth 0: they keep decoding (static batch)
            # but their cache scatters must stay in bounds until re-joined
            park = np.zeros(self.slots, bool)
            park[evicted] = True
            self.state["index"] = jnp.where(jnp.asarray(park), 0,
                                            self.state["index"])
            if met is not None:
                met.evictions.inc(len(evicted))
        if met is not None:
            met.occupancy.set(self.n_active / self.slots)
        return events

    def run(self) -> dict:
        """Drain: step until idle, tokens grouped per request id."""
        out: dict = {}
        while not self.idle():
            for ev in self.step():
                out.setdefault(ev.rid, []).append(ev.token)
        return out

    # -- the join half -----------------------------------------------------

    def _join(self) -> None:
        free = [i for i in range(self.slots) if self.active[i] is None]
        joins = list(zip(free, [self.queue.pop(0)
                                for _ in free[:len(self.queue)]]))
        if not joins:
            return
        eng, met = self.engine, self.metrics
        # right-pad prompts MINUS their last token; the first decode_step
        # feeds that token, so real token j always sits at cache slot j
        # with position j and pad keys are masked by the per-lane validity
        # bound — lane results don't depend on co-joiners' prompts.
        lens = [len(r.prompt) for _, r in joins]
        plen = self.prefill_len or _bucket(max(max(lens) - 1, 1))
        assert plen >= max(lens) - 1, \
            f"prefill_len={plen} shorter than a submitted prompt"
        # joiners packed first; shapes depend on the width alone
        toks = np.zeros((self.slots, plen), np.int32)
        lanes = np.zeros(self.slots, np.int32)
        cur, idx = np.asarray(self._cur).copy(), \
            np.asarray(self.state["index"]).copy()
        for j, (i, req) in enumerate(joins):
            toks[j, :lens[j] - 1] = req.prompt[:-1]
            lanes[j] = i
            cur[i] = req.prompt[-1]
            idx[i] = lens[j] - 1
            self.active[i] = req
            self._remaining[i] = req.max_new
        t0 = time.perf_counter()
        state = eng.join(toks, lanes, np.int32(len(joins)), self.state)
        state["index"] = jnp.asarray(idx, jnp.int32)
        self.state = state
        self._cur = jnp.asarray(cur)
        if met is not None:
            met.prefill_ms.observe(1e3 * (time.perf_counter() - t0))
            met.joins.inc(len(joins))
            met.prefill_tokens.inc(int(sum(lens)))
            met.prefill_positions.inc(len(joins) * plen)
            met.queue_depth.set(len(self.queue))
