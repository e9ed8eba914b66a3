"""Continuous-batching scheduler: the TPU replacement for event-loop concurrency.

The reference's concurrency story is four Node event loops and a per-client
debounce (SURVEY.md §2 strategy table, "request-level concurrency"). Here the
equivalent is slot-based continuous batching on one device mesh:

- the KV cache holds `batch_slots` independent sequences (cache row = slot)
- admission: a new request prefills into a free slot's cache line ONLY
  (engine.prefill_row slices that row out, runs a (1, bucket) forward, and
  writes it back in place) — admission cost is independent of batch width,
  and other slots' cache lines are never touched; the shared prompt prefix
  is copied from the engine's prefix KV instead of recomputed. Requests that
  WAIT TOGETHER behind that prefix are admitted together where the engine
  groups admissions (the paged one: ``admit_rows``): each does its host half
  alone, then one forward over the waiting suffixes' rows admits the group
  (``_admit_pending``, ``_launch_group``) — one read of the weights, not one
  a request
- decode advances ALL active slots together in chunked on-device loops
  (`chunk_steps` per dispatch): one host round-trip per chunk, not per token
  — a readback stalls the dispatch pipeline and idles the device while the
  host works — while keeping admission latency bounded by chunk_steps *
  per-token time
- per-slot grammar FSM state rides along on device; finished slots park

This is SURVEY.md §7 step 2's "continuous-batching scheduler" and hard part
(1): per-sequence FSM state with vectorized logit masks, no host round-trip
per token.
"""

from __future__ import annotations

import os
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.compilewatch import watch_compiles
from ..utils.steplog import ALLOC_SPAN, REQUEST_SPAN, span
from .engine import (
    ChunkResult,
    DecodeEngine,
    GenerationResult,
    _mask_sample_advance,
)
from .paged import PoolExhausted, PreparedAdmission

try:  # device faults must PROPAGATE out of per-request fences (a corrupted
    # engine must not be dispatched again); everything else fails alone
    from jax.errors import JaxRuntimeError as _DeviceFault
except ImportError:  # pragma: no cover - older jax
    from jaxlib.xla_extension import XlaRuntimeError as _DeviceFault


def _err_result(error: str, steps: int = 0,
                prefill_ms: float = 0.0) -> GenerationResult:
    """The one spelling of a typed per-request failure. Error prefixes are
    contract: ``shed:`` -> the brain answers 503 + Retry-After (retryable
    overload), ``quarantined:`` / ``poisoned:`` / ``cancelled:`` -> 500
    (do not retry the same bytes)."""
    return GenerationResult(text="", token_ids=[], prefill_ms=prefill_ms,
                            decode_ms=0.0, steps=steps, finished=False,
                            error=error)


@watch_compiles("scheduler._first_token_into_slot")
@partial(jax.jit, static_argnames=("greedy", "constrained", "kernels", "rules"))
def _first_token_into_slot(last_logits, state, rng, slot, n, start_state,
                           temperature, max_new_tokens, tables,
                           greedy: bool = True, constrained: bool = True,
                           kernels: str = "xla", rules=None, logit_mask=None):
    """The admission tail as ONE device program: split the batcher's key,
    pick the first token from the prefill's last-row logits (the same
    ``_mask_sample_advance`` the standalone ``engine._first_token`` runs),
    and write the admitted slot's entry of the six per-slot state arrays
    ``state`` = (cur, fsm, pos, nbytes, tokens_left, active). ``slot`` and
    ``n`` (the prompt length) are traced scalars: one compile serves every
    slot and length. Returns the six arrays and the batcher's next key."""
    rng, k = jax.random.split(rng)
    tok0, fsm0 = _mask_sample_advance(
        last_logits, start_state, tables, k, temperature, greedy,
        constrained, kernels, rules, logit_mask)
    cur, fsm, pos, nbytes, tokens_left, active = state
    return (cur.at[slot].set(tok0[0]), fsm.at[slot].set(fsm0[0]),
            pos.at[slot].set(n), nbytes.at[slot].set(0),
            tokens_left.at[slot].set(max_new_tokens),
            active.at[slot].set(True)), rng


def _first_tokens_into_slots(last_logits, state, slots, ns, rng, start_state,
                             temperature, max_new_tokens, tables, logit_mask=None, *,
                             greedy: bool = True, constrained: bool = True,
                             kernels: str = "xla", rules=None):
    """``_first_token_into_slot`` for a GROUP of admissions (ISSUE 35): the
    ``pick`` the engine's one group program (``paged.forward_paged_first_tokens``)
    runs on its logits — traced inside it, no launch of its own.
    ``last_logits`` (A, 1, V) is each row's last real position, ``slots`` /
    ``ns`` (A,) where and how long each admitted prompt is. A row the group
    does not fill names slot ``batch_slots``, past the state arrays: its pick
    is dropped. One key split a group (one an admission on the per-slot
    path: a sampled, non-greedy stream differs between the two, a greedy one
    does not). -> (the six state arrays, the next key)."""
    rng, k = jax.random.split(rng)
    A = last_logits.shape[0]
    tok0, fsm0 = _mask_sample_advance(
        last_logits.reshape(A, -1), jnp.broadcast_to(start_state, (A,)), tables, k,
        temperature, greedy, constrained, kernels, rules, logit_mask)
    cur, fsm, pos, nbytes, tokens_left, active = state
    put = lambda arr, v: arr.at[slots].set(v, mode="drop")
    return (put(cur, tok0), put(fsm, fsm0), put(pos, ns), put(nbytes, 0),
            put(tokens_left, max_new_tokens), put(active, True)), rng


@dataclass
class _Waiting:
    """A request whose HOST half of admission is done (``_admit`` with a
    group open): its slot is taken and holds its blocks, its ledger entry
    holds the host half; the launches are its group's."""

    rid: int
    slot: int
    prep: PreparedAdmission
    entry: dict | None  # its ``admissions`` entry in the step ledger
    t0: float
    t_enq: float
    queue_ms: float


@dataclass
class _Slot:
    request_id: int = -1
    token_ids: list = field(default_factory=list)
    start_s: float = 0.0
    prefill_ms: float = 0.0  # COMPUTED prefill only (cached KV costs tokens
    # of bookkeeping, not forward time — the split the HUD renders)
    prompt_len: int = 0
    cached_tokens: int = 0  # prompt tokens served from cached KV (static
    # prefix / radix chain) at admission
    queue_ms: float = 0.0  # submit() -> popped from pending by step()
    eos: bool = False
    # ISSUE 15 conf lanes accumulated across chunks (engines report per-row
    # margin/entropy/forced/decision lanes on the same combined readback)
    conf_msum: float = 0.0
    conf_mmin: float = float("inf")
    conf_esum: float = 0.0
    conf_forced: int = 0
    conf_cnt: int = 0
    # ISSUE 17 per-request resource ledger (utils.costmodel.LEDGER_KEYS,
    # all ints): set at admission when the cost lanes are on, folded per
    # chunk with the SAME int dict the engine meter totals — so
    # sum(per-request ledgers) == engine totals holds exactly
    cost: dict | None = None


class ContinuousBatcher:
    """Slot-based continuous batching over a DecodeEngine's model+cache.

    Synchronous core (submit/step/drain); services wrap it with a thread or
    asyncio executor. Every admitted request decodes concurrently with the
    others; new arrivals join at chunk boundaries.
    """

    def __init__(self, engine: DecodeEngine, chunk_steps: int = 32,
                 greedy: bool = True, temperature: float = 0.7,
                 byte_budget: int = 3900, max_new_tokens: int = 512):
        if engine.batch_slots < 1:
            raise ValueError("engine needs at least one batch slot")
        self.engine = engine
        self.B = engine.batch_slots
        self.chunk_steps = chunk_steps
        self.greedy = greedy
        self.temperature = temperature
        self.byte_budget = byte_budget
        self.max_new_tokens = max_new_tokens

        S = engine.max_len
        # device-resident per-slot state
        self.cur = jnp.full((self.B,), engine.pad_id, dtype=jnp.int32)
        self.pos = jnp.full((self.B,), S - 1, dtype=jnp.int32)
        self.fsm = jnp.zeros((self.B,), dtype=jnp.int32)
        self.active = jnp.zeros((self.B,), dtype=bool)
        self.nbytes = jnp.zeros((self.B,), dtype=jnp.int32)
        self.tokens_left = jnp.zeros((self.B,), dtype=jnp.int32)
        # what every admission writes, on the device once: the admission
        # tail (_first_token_into_slot) takes no host value but slot and n
        self._admit_consts = (
            jnp.full((1,), engine.fsm.start, dtype=jnp.int32),
            jnp.float32(temperature), jnp.int32(max_new_tokens))

        self.slots: list[_Slot] = [_Slot() for _ in range(self.B)]
        self.pending: list[tuple[int, str]] = []
        # enqueue timestamps keyed by request id (NOT widened pending
        # tuples — colocate's tombstone filter unpacks 2-tuples): TTFT must
        # cover queue wait, the component that actually degrades under load
        self._enqueued_at: dict[int, float] = {}
        self.results: dict[int, GenerationResult] = {}
        self._next_id = 0
        self._rng = jax.random.PRNGKey(1234)
        # host mirror of `active`: admission decisions must not pay a device
        # readback (each one waits for the device to drain); the mirror is
        # refreshed from the chunk's single combined device_get
        self._active_h = np.zeros((self.B,), dtype=bool)
        # rolling tokens/sec gauge (EMA over chunks): the throughput signal
        # continuous batching tunes against, without a scrape having to
        # difference the tokens_generated counter itself
        self._tps_ema = 0.0
        # ---- fault containment state (ISSUE 7) ----
        # per-request deadlines (x-deadline-ms propagated by the brain):
        # checked at dequeue (queue wait may have consumed the budget) and
        # between decode chunks (a dead/expired client must not burn steps)
        self._deadline: dict[int, object] = {}
        # repeat-offender quarantine: prompt fingerprint -> offense record.
        # A prompt that poisons the engine QUARANTINE_AFTER times is refused
        # at submit — the same poisonous bytes retried by a client (or
        # mirrored across sessions) must not keep evicting slots. Bounded
        # LRU; surfaced in the brain's /health.
        self.quarantine_after = int(os.environ.get("QUARANTINE_AFTER", "2"))
        self._offenses: "OrderedDict[object, dict]" = OrderedDict()
        self._prompt_fp: dict[int, object] = {}
        # chaos drill arming (slots flagged at admission) + epoch fence:
        # reset()/warm-restart bumps _epoch so a step that was stalled
        # mid-flight discards its commit instead of scribbling on the
        # restarted world
        self._nan_slots: set[int] = set()
        self._epoch = 0
        # pool-pressure backpressure: first-PoolExhausted timestamp per rid;
        # a request that cannot be admitted within SCHED_POOL_WAIT_S (while
        # other slots could still free blocks) sheds with a typed error the
        # brain maps to 503 + Retry-After
        self._pool_wait: dict[int, float] = {}
        self._pool_wait_s = float(os.environ.get("SCHED_POOL_WAIT_S", "1.0"))
        # containment counters exist from construction (same discipline as
        # the breaker-state gauges: a scraper must see every containment
        # signal at zero, not as an absent series) — these literals are
        # also what tools/metrics_lint.py pins, since the eviction helper
        # increments through a parameter
        from ..utils import get_metrics

        m = get_metrics()
        m.inc("scheduler.slots_quarantined", 0.0)
        m.inc("scheduler.cancelled", 0.0)
        m.inc("scheduler.shed_expired", 0.0)
        # cost & efficiency observatory (ISSUE 17): the analytic meter the
        # per-chunk fold reconciles measured walls against. Pure host
        # arithmetic over readbacks the chunk already paid for — the
        # decode path is token-identical with the lanes on or off.
        from ..utils.costmodel import CostMeter, cost_enabled

        self.costs: CostMeter | None = (
            CostMeter(engine) if cost_enabled() else None)
        # multi-tenant QoS plane (ISSUE 18): constructed only when the
        # TENANT_CLASSES knob is set — unset keeps every path below
        # byte-identical to the single-tenant scheduler (pop(0) admission,
        # no preemption, unsalted radix keys)
        from .tenancy import TenancyPlane, tenancy_enabled

        self.tenancy = TenancyPlane() if tenancy_enabled() else None
        self._tenant: dict[int, str | None] = {}   # rid -> wire tenant tag
        self._prompt_src: dict[int, object] = {}   # rid -> prompt (preempt requeue)
        self._preempted: dict[int, int] = {}       # rid -> preemption count
        self._preempt_on = os.environ.get("TENANT_PREEMPT", "1") != "0"
        # satellite fix (ISSUE 18): a pool-starved head requeue ages out —
        # after SCHED_REQUEUE_MAX head retries the oversized waiter rotates
        # to the back so smaller requests queued behind it get an attempt
        self._requeues: dict[int, int] = {}
        self._requeue_max = int(os.environ.get("SCHED_REQUEUE_MAX", "8"))
        m.inc("scheduler.requeue_rotations", 0.0)
        # incremental streaming prefill (ISSUE 19): PREFILL_CHUNK_TOKENS
        # splits any prompt admission into chunked prefills interleaved
        # with decode chunks (paged engines only — duck-typed on
        # begin_chunked_prefill); unset keeps the one-shot barrier prefill
        # byte-identical. _admitting maps a reserved slot (request_id set,
        # active False — _free_slot skips it) to its (cursor, enqueue_ts,
        # queue_ms, staged (slot, n) for the admission tail).
        pc = os.environ.get("PREFILL_CHUNK_TOKENS")
        self._prefill_chunk = int(pc) if pc else 0
        self._admitting: dict[int, tuple] = {}
        if self._prefill_chunk:
            m.inc("prefill.chunked_admissions", 0.0)
            m.inc("prefill.chunks", 0.0)
        # prefix-feed counters (ISSUE 19) exist from construction, same
        # scrape-at-zero discipline as the containment counters above
        m.inc("prefill.feeds", 0.0)
        m.inc("prefill.feeds_committed", 0.0)
        m.inc("prefill.feeds_shed", 0.0)
        if self.tenancy is not None:
            m.inc("tenant.throttled", 0.0)
            m.inc("tenant.preemptions", 0.0)
            # per-tenant radix namespaces: the trees charge over-quota
            # inserts to the owning tenant's own leaves (serve.radix)
            for rc in engine.radix or ():
                rc.ns_quota = self.tenancy.block_quota

    # ------------------------------------------------------------ warm-up

    def warmup(self) -> None:
        """Compile, BEFORE the serving loop takes traffic, what a request
        can make it dispatch: the admission prefill at every suffix bucket
        behind the installed prompt prefix (every full-prompt bucket when
        there is none), the first-token pick, the grouped admission,
        and one decode chunk with its readback, at every width the
        chunk program has. A cold compile
        inside ``step()`` stalls every batch-mate and runs under the colocate stall watchdog (``ENGINE_STALL_S``):
        a burst landing in several uncompiled buckets at once would outlast
        it and get the healthy engine warm-restarted. MUST run on the thread
        that drives ``step()``, with nothing in flight."""
        from ..utils.steplog import get_steplog

        eng = self.engine
        prefix = list(eng.prefix_ids)
        for b in eng.suffix_buckets + tuple(eng.prefill_buckets):
            if len(prefix) + b > eng.max_len:
                break
            try:
                eng.prefill_slot(prefix + [eng.pad_id] * b, 0)
            except PoolExhausted:
                break  # a pool this small sheds such a prompt in serving too
            finally:
                eng.release_slot(0, ok=False)
        # the grouped admission (ISSUE 35), RUN and not only compiled: two
        # requests waiting beside two free slots are a group; its launches
        # alone, no chunk behind them
        if eng.admit_rows and len(prefix) + eng.GROUP_BUCKET <= eng.max_len:
            rids = [self.submit(prefix + [eng.pad_id] * 8) for _ in range(2)]
            timer = get_steplog().timer()
            try:
                self._admit_pending(timer, self._active_h)
            finally:
                timer.close()
            for rid in rids:
                self.cancel(rid, "warm-up")
                self.results.pop(rid, None)
        rid = self.submit(prefix + [eng.pad_id] * 8)
        res = self.step()
        if res is not None and res.rows != self.B:
            # that lone request rode the compacted width: RUN the full one
            # too, on the same row, for the one forward a token budget of 1
            # allows (a program entered with nothing live runs no forward,
            # and the first one it then runs in traffic is slower: 30-80 ms
            # on a v5e, PERF.md section 6, PR 29). What it returns is dropped;
            # the request is cancelled next, its blocks with it
            eng.decode_chunk(
                self.cur, self.pos, self.fsm, self.active, self.nbytes,
                jnp.minimum(self.tokens_left, 1), self._rng, self.temperature,
                self.byte_budget, self.chunk_steps, self.greedy)
        self.cancel(rid, "warm-up")
        self.results.pop(rid, None)

    # ------------------------------------------------------------ submit

    def reset(self) -> None:
        """Abandon all queued and in-flight work (decode-fault recovery —
        the cache contents are garbage until fresh admissions overwrite
        them, which _admit and chunk_decode_loop handle per slot). Bumps
        the epoch so a step stalled mid-flight (the case the watchdog
        warm-restarts around) discards its commit on wake instead of
        scribbling stale device state over the fresh world. The quarantine
        list deliberately SURVIVES — a poisonous prompt stays quarantined
        across the restart it caused."""
        self._epoch += 1
        self.pending.clear()
        self._enqueued_at.clear()
        self._deadline.clear()
        self._prompt_fp.clear()
        self._pool_wait.clear()
        self._nan_slots.clear()
        self._tenant.clear()
        self._prompt_src.clear()
        self._preempted.clear()
        self._requeues.clear()
        self._admitting.clear()
        if self.tenancy is not None:
            self.tenancy.reset_occupancy()
        self.results.clear()
        self.slots = [_Slot() for _ in range(self.B)]
        self.active = jnp.zeros_like(self.active)
        self._active_h = np.zeros((self.B,), dtype=bool)
        for b in range(self.B):
            self.engine.release_slot(b, ok=False)

    def submit(self, prompt, deadline=None, tenant=None) -> int:
        """Queue one request. ``prompt`` is a string, or a pre-tokenized
        ``list[int]`` — the session-aware brain path builds turn N's ids as
        the literal turn N-1 ids + generated ids + new-frame ids, so the
        radix match sees a STRICT token extension (re-encoding generated
        text is not id-stable: grammar-constrained decoding may emit
        non-canonical BPE pieces). ``deadline`` (utils.resilience.Deadline,
        optional) arms queue-expiry shedding and mid-decode cancellation.
        ``tenant`` (ISSUE 18) tags the request's QoS lane when the tenancy
        plane is on; a rate-limited lane is refused here with the retryable
        ``shed:`` prefix (503 + Retry-After at the brain — throttled, not
        errored). A quarantined prompt (repeat poison offender) is refused
        with a typed error, before it can occupy queue or slot."""
        rid = self._next_id
        self._next_id += 1
        fp = self._fingerprint(prompt)
        off = self._offenses.get(fp)
        if off is not None and off["count"] >= self.quarantine_after:
            off["rejected"] += 1
            from ..utils import get_metrics

            get_metrics().inc("scheduler.quarantine_rejected")
            self.results[rid] = _err_result(
                f"quarantined: {off['reason']} x{off['count']} "
                f"(prompt {off['preview']!r})")
            return rid
        if self.tenancy is not None:
            if not self.tenancy.admit(tenant):
                from ..utils import get_metrics

                get_metrics().inc("tenant.throttled")
                self.results[rid] = _err_result(
                    f"shed: tenant {self.tenancy.resolve(tenant)} rate-limited")
                return rid
            self._tenant[rid] = tenant
            self._prompt_src[rid] = prompt
            self.tenancy.on_queue(tenant)
        self._prompt_fp[rid] = fp
        if deadline is not None:
            self._deadline[rid] = deadline
        self._enqueued_at[rid] = time.perf_counter()
        self.pending.append((rid, prompt))
        return rid

    # ------------------------------------------------- fault containment

    @staticmethod
    def _fingerprint(prompt) -> object:
        return prompt if isinstance(prompt, str) else tuple(prompt)

    @staticmethod
    def _preview(prompt) -> str:
        return (prompt[:60] if isinstance(prompt, str)
                else f"<{len(prompt)} token ids>")

    def _record_offense(self, rid: int, reason: str) -> None:
        """Count a poison event against the request's prompt fingerprint;
        at ``quarantine_after`` the fingerprint is refused at submit."""
        fp = self._prompt_fp.get(rid)
        if fp is None:
            return
        off = self._offenses.get(fp)
        if off is None:
            off = self._offenses[fp] = {
                "count": 0, "rejected": 0, "reason": reason,
                "preview": self._preview(fp)}
        off["count"] += 1
        off["reason"] = reason
        self._offenses.move_to_end(fp)
        while len(self._offenses) > 64:
            self._offenses.popitem(last=False)

    def quarantined(self) -> list[dict]:
        """Active quarantine entries (the brain surfaces these in /health)."""
        return [
            {"preview": off["preview"], "count": off["count"],
             "rejected": off["rejected"], "reason": off["reason"]}
            for off in self._offenses.values()
            if off["count"] >= self.quarantine_after
        ]

    def _cleanup(self, rid: int) -> None:
        """Drop every per-request map entry (terminal paths only)."""
        self._enqueued_at.pop(rid, None)
        self._deadline.pop(rid, None)
        self._prompt_fp.pop(rid, None)
        self._pool_wait.pop(rid, None)
        self._tenant.pop(rid, None)
        self._prompt_src.pop(rid, None)
        self._preempted.pop(rid, None)
        self._requeues.pop(rid, None)

    def _evict_slot(self, b: int, error: str, counter: str) -> None:
        """Evict ONE in-flight slot with a typed error: deactivate the
        device row, free the engine's KV refs WITHOUT caching its chain
        (``ok=False`` — a poisoned/cancelled generation must never be
        served to a later session as a warm radix prefix), and resolve the
        request. Batch-mates' rows are untouched — their carries never see
        the eviction, so their tokens are identical to an undisturbed run."""
        from ..utils import get_metrics

        sl = self.slots[b]
        rid = sl.request_id
        res = _err_result(error, steps=len(sl.token_ids),
                          prefill_ms=sl.prefill_ms)
        # an evicted row still accounts the cost it spent before dying —
        # without this the ledger would leak exactly the work the poison/
        # cancellation burned (ISSUE 17 conservation covers errored rows)
        res.cost = dict(sl.cost) if sl.cost is not None else None
        self.results[rid] = res
        get_metrics().inc(counter)
        if self.tenancy is not None:
            t = self._tenant.get(rid)
            self.tenancy.on_release(t)
            self.tenancy.fold_cost(t, res.cost)
        self._cleanup(rid)
        self.slots[b] = _Slot()
        self.active = self.active.at[b].set(False)
        self._active_h[b] = False
        self._nan_slots.discard(b)
        # a slot evicted mid-chunked-prefill (ISSUE 19) drops its cursor;
        # release below frees the admission's blocks (no radix insert —
        # the engine only marks the chain insertable at the final chunk)
        self._admitting.pop(b, None)
        self.engine.release_slot(b, ok=False)

    def cancel(self, rid: int, reason: str = "client gone") -> bool:
        """Cancel one request mid-flight: queued -> dropped; in a slot ->
        evicted between decode chunks, releasing the slot and its KV blocks
        instead of burning steps for a dead socket. MUST run on the thread
        that drives step() (colocate applies cancellations there); returns
        True when the request was found live."""
        from ..utils import get_metrics

        for i, (r, _) in enumerate(self.pending):
            if r == rid:
                del self.pending[i]
                self.results[rid] = _err_result(f"cancelled: {reason}")
                get_metrics().inc("scheduler.cancelled")
                if self.tenancy is not None:
                    self.tenancy.on_dequeue(self._tenant.get(rid),
                                            admitted=False)
                self._cleanup(rid)
                return True
        for b in range(self.B):
            if self.slots[b].request_id == rid:
                self._evict_slot(b, f"cancelled: {reason}", "scheduler.cancelled")
                return True
        return False

    def _preempt_slot(self, b: int) -> None:
        """Chunk-boundary preemption (ISSUE 18): vacate ONE over-budget slot
        for a starved lane, through the same release seam cancellation uses
        — but preempted-not-errored. The slot's prompt+generated chain is
        inserted into its tenant's radix namespace (``ok=True`` release),
        the spent cost folds into the tenant ledger, and the ORIGINAL prompt
        requeues at the head: greedy decode is deterministic, so
        re-admission replays the same stream as a warm prefill off its own
        chain — resume is a warm admission, and the request's result arrives
        late instead of failing. Bounded to one preemption per request so a
        tight pool can never livelock two lanes trading the same slot."""
        from ..utils import get_metrics

        sl = self.slots[b]
        rid = sl.request_id
        t = self._tenant.get(rid)
        prompt = self._prompt_src.get(rid)
        if prompt is None:  # no requeue source — leave the slot alone
            return
        self._preempted[rid] = self._preempted.get(rid, 0) + 1
        if self.tenancy is not None:
            self.tenancy.fold_cost(t, sl.cost)
            self.tenancy.on_release(t)
            self.tenancy.on_queue(t)
            self.tenancy.note_preemption(t)
        get_metrics().inc("tenant.preemptions")
        # warm release: prompt+generated adopted by the tenant's namespace,
        # so the re-admission's prefill is served from cache
        self.engine.release_slot(b, generated_ids=sl.token_ids)
        self.slots[b] = _Slot()
        self.active = self.active.at[b].set(False)
        self._active_h[b] = False
        self._nan_slots.discard(b)
        self._enqueued_at[rid] = time.perf_counter()
        self.pending.insert(0, (rid, prompt))

    def _free_slot(self, act: np.ndarray) -> int | None:
        for b in range(self.B):
            if not act[b] and self.slots[b].request_id < 0:
                return b
        return None

    def _admit(self, slot: int, rid: int, prompt: str, timer,
               queue_ms: float, group: list | None = None) -> str:
        """One request's admission into ``slot``, or its HOST half.

        With no ``group`` (one request waiting, or the engine groups none):
        prefill ONE slot's cache line through ``engine.prefill_slot`` — a
        (1, bucket) forward, whatever the batch width — reusing the engine's
        shared-prefix KV when the prompt starts with it; "live" back.

        With a ``group`` open (ISSUE 35: several requests wait beside as many
        free slots), the request does here what can fail for it ALONE —
        tokenize, ``engine.prepare_admission``: the slot's release, the
        prefix match, its blocks — and joins the group as a ``_Waiting``;
        "waiting" back. ``_launch_group`` then admits the group with one
        forward over its members' rows (not every SLOT's row, which round 1
        computed for each admission: 32x wasted FLOPs at 32 slots). A prompt
        the engine does not group (no prefix match, a long suffix) takes the
        per-slot path as above.

        "chunked" back when a CHUNKED admission was started instead (ISSUE
        19, PREFILL_CHUNK_TOKENS set, long prompt, engine supports it):
        the slot is reserved — request_id set, active stays False — and
        ``_advance_admissions`` runs one prefill chunk per step until the
        final chunk lands, so a 1k-token cold prompt never head-of-line-
        blocks batch-mates' decode chunks behind a barrier prefill.

        Each request is ONE ``sched.admit.request`` span whose parts
        (utils.steplog.ADMISSION_PARTS; the engine writes ``.alloc`` and
        ``.prefill_call``) tile it; a raise drops its ledger entry with it.
        A waiting request's span is its host half, and its entry gains its
        share of the group's launches when they run."""
        from ..utils import get_metrics

        eng = self.engine
        with timer.span(REQUEST_SPAN, rid=rid, queue_ms=round(queue_ms, 3)) as req:
            if self.tenancy is not None:
                # tenant radix namespace (ISSUE 18): the slot's cache chains
                # are salted with the resolved class name so one tenant's
                # churn cannot evict another's warm chains (serve.radix)
                with span(f"{REQUEST_SPAN}.bookkeeping"):
                    eng.set_slot_ns(
                        slot, self.tenancy.resolve(self._tenant.get(rid)))
            t0 = time.perf_counter()
            with span(f"{REQUEST_SPAN}.tokenize"):
                ids, reused = eng.encode_prompt(prompt)
            n = len(ids)
            req.set(prompt_tokens=n, head_ids_reused=reused)
            get_metrics().inc("admit.head_ids_reused", float(reused))
            C = self._prefill_chunk
            if C > 0 and n > C:
                with span(ALLOC_SPAN):
                    cursor = eng.begin_chunked_prefill(ids, slot, C)
                if cursor is not None:
                    sl = self.slots[slot]
                    sl.request_id = rid
                    sl.token_ids = []
                    sl.start_s = t0
                    sl.prompt_len = n
                    sl.eos = False
                    # the enqueue stamp travels with the cursor: TTFT
                    # still covers queue wait + every interleaved
                    # prefill chunk (and the queue wait its own number)
                    self._admitting[slot] = (
                        cursor, self._enqueued_at.pop(rid, t0), queue_ms,
                        self._stage_slot(slot, n))
                    get_metrics().inc("prefill.chunked_admissions")
                    req.drop()  # the admission lands with its last chunk
                    return "chunked"
            prep = eng.prepare_admission(ids, slot) if group is not None else None
            if prep is not None:
                with span(f"{REQUEST_SPAN}.bookkeeping"):
                    # taken: ``_free_slot`` passes it by until the launch
                    self.slots[slot].request_id = rid
                    req.set(cached_tokens=prep.cached)
                    group.append(_Waiting(rid, slot, prep, req.entry, t0,
                                          self._enqueued_at.pop(rid, t0), queue_ms))
                return "waiting"
            slot_n = self._stage_slot(slot, n)
            last_logits = eng.prefill_slot(ids, slot)
            self._finish_admission(slot, rid, n, slot_n, last_logits, t0,
                                   self._enqueued_at.pop(rid, t0), queue_ms,
                                   eng._last_prefill_compute_ms, eng._last_cached_tokens)
            req.set(cached_tokens=self.slots[slot].cached_tokens, rows=1)
            self._count_call(1)
        return "live"

    @staticmethod
    def _count_call(rows: int) -> None:
        """One device prefill call that admitted ``rows`` requests."""
        from ..utils import get_metrics

        m = get_metrics()
        m.inc("admit.calls")
        m.inc("admit.rows", float(rows))
        if rows > 1:
            m.inc("admit.batched_rows", float(rows))

    def _launch_group(self, group: list, timer) -> None:
        """The DEVICE half of the waiting requests' admissions, once for the
        group: ``engine.admit_group`` — ONE program: table rows, prefix tails,
        the forward, and ``_first_tokens_into_slots`` on its logits — then
        each member's books. On the trace ``sched.admit.group`` holds the
        launch's spans (``.alloc``, ``.slot_state`` for its one host→device
        copy, ``.prefill_call`` once a call);
        in the ledger what it took is shared out evenly over the members'
        entries, which gain ``rows``, so a step's entries still sum to its
        admit and prefill stages. A group of one runs the per-slot programs
        (``admit_group`` says how). A fault here is the device's or the
        program's, never one member's prompt: a device fault propagates, as
        from ``_admit``; anything else fails every member, typed."""
        eng = self.engine
        lone = group[0] if len(group) == 1 else None
        try:
            with timer.group([w.entry for w in group]):
                if lone is not None:
                    slot_n = self._stage_slot(lone.slot, lone.prep.n)
                    out = eng.admit_group([lone.prep])
                    (rec,) = out.records
                    self._finish_admission(lone.slot, lone.rid, lone.prep.n, slot_n,
                                           out.logits, lone.t0, lone.t_enq, lone.queue_ms,
                                           rec.compute_ms, rec.cached_tokens)
                    self._count_call(1)
                    return
                out = eng.admit_group(
                    [w.prep for w in group], pick=_first_tokens_into_slots,
                    state=(self.cur, self.fsm, self.pos, self.nbytes, self.tokens_left,
                           self.active),
                    pick_args=(self._rng, *self._admit_consts, eng.tables, eng.logit_mask),
                    pick_kw=(("greedy", self.greedy), ("constrained", True),
                             ("kernels", eng.kernels), ("rules", eng.rules)))
                (self.cur, self.fsm, self.pos, self.nbytes, self.tokens_left,
                 self.active), self._rng = out.picked
                with span(f"{REQUEST_SPAN}.bookkeeping"):
                    for w, rec in zip(group, out.records):
                        self._book_admission(w.slot, w.rid, w.prep.n, w.t0, w.t_enq,
                                             w.queue_ms, rec.compute_ms, rec.cached_tokens)
                    self._count_call(len(group))
        except Exception as e:
            if isinstance(e, _DeviceFault):
                raise
            for w in group:
                self._record_offense(w.rid, f"prefill {type(e).__name__}")
                self._evict_slot(w.slot, str(e), "scheduler.prefill_faults")

    @staticmethod
    def _stage_slot(slot: int, n: int):
        """The two host values the admission tail needs, put on the device
        BEFORE the prefill is dispatched: nothing after that launch then
        waits for a host→device copy."""
        with span(f"{REQUEST_SPAN}.slot_state"):
            return jax.device_put((np.int32(slot), np.int32(n)))

    def _finish_admission(self, slot: int, rid: int, n: int, slot_n,
                          last_logits, t0: float, t_enq: float, queue_ms: float,
                          prefill_ms: float, cached_tokens: int) -> None:
        """The admission tail shared by one-shot and chunked prefills: ONE
        launch (``_first_token_into_slot``: key split, fused grammar-mask
        first-token sample, the slot's six state entries) on arrays already
        on the device, then slot bookkeeping, TTFT and queue wait, and the
        prefill cost fold. Every in-chunk instance of the mask→sample tail
        is jit-inlined inside the decode loops; this is its one
        host-dispatched instance, under the same ``grammar_mask_sample``
        scope (a group's is ``_first_tokens_into_slots``)."""
        eng = self.engine
        with span(f"{REQUEST_SPAN}.slot_state"):
            (self.cur, self.fsm, self.pos, self.nbytes, self.tokens_left,
             self.active), self._rng = _first_token_into_slot(
                last_logits,
                (self.cur, self.fsm, self.pos, self.nbytes, self.tokens_left,
                 self.active),
                self._rng, *slot_n, *self._admit_consts, eng.tables,
                greedy=self.greedy, constrained=True, kernels=eng.kernels,
                rules=eng.rules, logit_mask=eng.logit_mask)
        with span(f"{REQUEST_SPAN}.bookkeeping"):
            self._book_admission(slot, rid, n, t0, t_enq, queue_ms, prefill_ms,
                                 cached_tokens)

    def _book_admission(self, slot: int, rid: int, n: int, t0: float,
                        t_enq: float, queue_ms: float, prefill_ms: float,
                        cached_tokens: int) -> None:
        """The host-only end of an admission (``.bookkeeping``)."""
        sl = self.slots[slot]
        sl.request_id = rid
        sl.token_ids = []
        sl.start_s = t0
        sl.prompt_len = n
        # prefill_ms = COMPUTED suffix dispatch only (the old wall-clock
        # number conflated cached-prefix bookkeeping with real forward
        # time); cached_tokens carries the part the cache absorbed. Both are
        # the engine's to say: ``prefill_slot`` leaves them on itself,
        # ``admit_group`` returns them
        sl.prefill_ms = prefill_ms
        sl.cached_tokens = int(cached_tokens)
        sl.queue_ms = queue_ms
        sl.eos = False
        # TTFT: ENQUEUE through the first sampled token — queue wait
        # included, because that is the component that degrades when all
        # slots are busy (a prefill-only number stays flat exactly when
        # real time-to-first-token blows up). The streaming-serving
        # headline metric (WhisperFlow/WhisperKit report it first-class).
        from ..utils import get_metrics

        m = get_metrics()
        m.observe_ms("scheduler.ttft", (time.perf_counter() - t_enq) * 1e3)
        # queue wait as its own number: submit() -> popped from ``pending``
        # (a pool-starved requeue keeps its first stamp)
        m.observe_ms("scheduler.queue_wait", queue_ms)
        # prefill cost fold (ISSUE 17): an exact cached-vs-computed
        # partition of the cold-prompt cost — the same ints land in the
        # slot ledger and the meter totals, so conservation is exact
        if self.costs is not None:
            computed, cached = self.costs.model.prefill_split(
                n, sl.cached_tokens)
            sl.cost = dict.fromkeys(
                ("decode_flops", "decode_bytes", "kv_block_us"), 0)
            sl.cost["prefill_flops"] = computed
            sl.cost["prefill_cached_flops"] = cached
            self.costs.fold_prefill(computed, cached, sl.prefill_ms)

    def _advance_admissions(self, act: np.ndarray, timer) -> tuple[int, int]:
        """Advance every in-flight chunked admission by ONE prefill chunk
        (ISSUE 19). A slot whose final chunk lands finishes admission and
        goes active for this step's decode chunk; earlier chunks cost one
        bounded ``(1, C)`` dispatch each, interleaved with batch-mates'
        decode chunks instead of stalling them behind a barrier prefill.
        Returns (completed, chunks_stepped) for the step ledger; the
        engine's ``.prefill_call`` spans are its prefill stage."""
        if not self._admitting:
            return 0, 0
        from ..utils import get_metrics
        from ..utils.chaos import chaos_fire

        m = get_metrics()
        eng = self.engine
        done, stepped = 0, 0
        for slot in sorted(self._admitting):
            cursor, t_enq, queue_ms, slot_n = self._admitting[slot]
            rid = self.slots[slot].request_id
            with timer.span(REQUEST_SPAN, rid=rid, queue_ms=round(queue_ms, 3),
                            prompt_tokens=self.slots[slot].prompt_len) as req:
                try:
                    last_logits = eng.chunked_prefill_step(cursor)
                except Exception as e:
                    if isinstance(e, _DeviceFault):
                        raise  # corrupted engine: never per-request (see step)
                    # per-request chunk fence: the admission fails alone, its
                    # blocks release through the ordinary eviction seam
                    if not isinstance(e, ValueError):
                        self._record_offense(rid, f"prefill {type(e).__name__}")
                    self._evict_slot(slot, str(e), "scheduler.prefill_faults")
                    req.drop()
                    continue
                stepped += 1
                m.inc("prefill.chunks")
                m.inc("admit.calls")
                if last_logits is None:
                    req.drop()  # a middle chunk: on the trace, no admission
                    continue
                self._admitting.pop(slot, None)
                self._finish_admission(slot, rid, self.slots[slot].prompt_len,
                                       slot_n, last_logits,
                                       self.slots[slot].start_s, t_enq, queue_ms,
                                       eng._last_prefill_compute_ms,
                                       eng._last_cached_tokens)
                m.inc("admit.rows")
                req.set(cached_tokens=self.slots[slot].cached_tokens, rows=1)
            act[slot] = True
            done += 1
            # chaos drill arming matches the one-shot admission path
            if chaos_fire("nan_logits"):
                self._nan_slots.add(slot)
            if chaos_fire("dead_fsm"):
                self.fsm = self.fsm.at[slot].set(-1)
        return done, stepped

    # ------------------------------------------------------------ feeds

    def feed_prefix(self, prompt, tenant=None) -> dict:
        """Prefill-only admission (ISSUE 19 prefix feed): render ``prompt``
        through a transiently borrowed free slot, commit the computed
        chain into the radix tree, and release — all inside one call on
        the serving-loop thread, so no decode slot is ever held across a
        step. The radix re-extension makes an incremental feed O(new
        tokens): each feed's prefill starts from the longest cached prefix
        (usually the previous feed's chain), and the eventual real parse
        admits warm with ``prefill_remaining ≈ 0``.

        Best-effort and sheddable BY DESIGN — live work always wins: a
        feed sheds when real requests are queued, when no slot is free,
        or when the pool is exhausted, and a shed feed costs the caller
        nothing but the prefill-ahead it was trying to buy. ``tenant``
        salts the cached chain into the lane's radix namespace (ISSUE 18),
        so fed chains count against that tenant's block quota."""
        from ..utils import get_metrics

        m = get_metrics()
        m.inc("prefill.feeds")
        eng = self.engine
        if eng.radix is None:
            return {"ok": False, "reason": "radix_off"}
        if self.pending:
            m.inc("prefill.feeds_shed")
            return {"ok": False, "reason": "busy"}
        slot = self._free_slot(self._active_h)
        if slot is None:
            m.inc("prefill.feeds_shed")
            return {"ok": False, "reason": "no_slot"}
        if self.tenancy is not None:
            eng.set_slot_ns(slot, self.tenancy.resolve(tenant))
        ids, _ = eng.encode_prompt(prompt)
        try:
            eng.prefill_slot(ids, slot)
        except PoolExhausted:
            try:
                eng.release_slot(slot, ok=False)
            except Exception:
                pass
            m.inc("prefill.feeds_shed")
            return {"ok": False, "reason": "pool_exhausted"}
        except Exception as e:
            if isinstance(e, _DeviceFault):
                raise
            try:
                eng.release_slot(slot, ok=False)
            except Exception:
                pass
            return {"ok": False, "reason": f"{type(e).__name__}: {e}"}
        cached = int(eng._last_cached_tokens)
        # generated_ids=[] (not None): release's ok-path radix insert fires
        # with the fed prompt alone — the tree adopts its full blocks, so
        # everything is either cached or freed before this call returns
        # (zero leaked refcounts by construction)
        eng.release_slot(slot, generated_ids=[], ok=True)
        m.inc("prefill.feeds_committed")
        return {"ok": True, "prompt_tokens": len(ids),
                "cached_tokens": cached}

    def prefill_export(self, prompt, *, stream_blocks: int = 4, emit=None,
                       stream_id=None, tenant=None) -> dict:
        """Prefill-only admission that EXPORTS the computed chain (disagg,
        ISSUE 20): ``feed_prefix`` generalized to arbitrary prompts on a
        prefill-pool replica, chunk-pipelined so transfer overlaps
        compute. Runs the prompt through a transiently borrowed slot via
        ``begin_chunked_prefill`` (chunk = ``stream_blocks`` pool blocks);
        after each chunk, every newly COMPLETE full block behind the
        compute frontier is gathered (``gather_chain_kv``) and handed to
        ``emit`` as one packed ``kv_seg`` blob — the first segments ship
        while later chunks still prefill. The chain then commits into the
        LOCAL radix tree too (``release_slot(generated_ids=[], ok=True)``,
        the feed_prefix zero-leak idiom), so a repeat export is pure cache.

        Shipped blocks stop at ``(len(ids) - 1) // block_size`` — the
        admission-side ``match`` limit — so the decode home can serve
        every streamed token. Sheds exactly like feed_prefix (busy /
        no_slot / pool_exhausted / radix_off); any shed or fault after
        segments were emitted leaves the receiver a torn stream, which
        the adopter commits partially — clean-or-cold by construction.
        Serving-loop thread only."""
        from ..utils import get_metrics

        from .handoff import pack_kv_segment

        m = get_metrics()
        m.inc("disagg.exports")
        eng = self.engine
        if eng.radix is None:
            m.inc("disagg.exports_shed")
            return {"ok": False, "reason": "radix_off"}
        if self.pending:
            m.inc("disagg.exports_shed")
            return {"ok": False, "reason": "busy"}
        slot = self._free_slot(self._active_h)
        if slot is None:
            m.inc("disagg.exports_shed")
            return {"ok": False, "reason": "no_slot"}
        if self.tenancy is not None:
            eng.set_slot_ns(slot, self.tenancy.resolve(tenant))
        ids, _ = eng.encode_prompt(prompt)
        bs = eng.block_size
        pb = len(eng._prefix_blocks[0])
        ship_cap = (len(ids) - 1) // bs
        n_ship = max(1, int(stream_blocks))
        sent = pb
        segments = 0

        def _ship(upto: int, final: bool) -> None:
            nonlocal sent, segments
            upto = min(int(upto), ship_cap)
            if emit is None or upto <= sent:
                return
            if not final and upto - sent < n_ship:
                return  # accumulate until a full segment's worth is ready
            chain = eng.slot_chain_blocks(slot)
            blob = pack_kv_segment(eng, ids, chain[sent:upto], sent,
                                   stream_id=stream_id)
            emit(blob)
            m.inc("disagg.blocks_streamed", float(upto - sent))
            sent = upto
            segments += 1

        try:
            cur = eng.begin_chunked_prefill(ids, slot, n_ship * bs)
            if cur is None:
                # short suffix / mostly cached: one-shot, single segment
                eng.prefill_slot(ids, slot)
            else:
                logits = None
                while logits is None:
                    logits = eng.chunked_prefill_step(cur)
                    frontier = cur.P + min(cur.j * cur.C, len(cur.suffix))
                    _ship(frontier // bs, final=False)
        except PoolExhausted:
            try:
                eng.release_slot(slot, ok=False)
            except Exception:
                pass
            m.inc("disagg.exports_shed")
            return {"ok": False, "reason": "pool_exhausted",
                    "segments": segments}
        except Exception as e:
            if isinstance(e, _DeviceFault):
                raise
            try:
                eng.release_slot(slot, ok=False)
            except Exception:
                pass
            m.inc("disagg.exports_shed")
            return {"ok": False, "reason": f"{type(e).__name__}: {e}",
                    "segments": segments}
        cached = int(eng._last_cached_tokens)
        try:
            _ship(ship_cap, final=True)
        except Exception:
            # a dead emit sink mid-final is the receiver's torn stream,
            # not our leak: commit the chain locally regardless
            pass
        eng.release_slot(slot, generated_ids=[], ok=True)
        return {"ok": True, "prompt_tokens": len(ids),
                "cached_tokens": cached, "chain_tokens": sent * bs,
                "segments": segments}

    # ------------------------------------------------------------ step

    def step(self) -> ChunkResult | None:
        """Admit pending requests into free slots, then run one chunk, and
        return that chunk's record (None: nothing was live, or the watchdog
        restarted the world under the step).

        Containment happens at the chunk boundaries: expired requests are
        shed at dequeue (``scheduler.shed_expired``) and cancelled between
        chunks (``scheduler.cancelled``); admission failures fence
        per-request (device faults still propagate); poisoned rows reported
        by the decode loop are quarantined (``scheduler.slots_quarantined``)
        — in every case batch-mates continue token-identically."""
        from ..utils.chaos import chaos_fire
        from ..utils.steplog import get_steplog

        epoch = self._epoch
        # the step ledger (ISSUE 9): one StepTimer per scheduler step, a
        # ``sched.step`` on the profiler's trace whose four contiguous
        # stage spans tile the chunk wall. Host timing only — record()
        # no-ops when STEPLOG_ENABLE=0, and the decode path is
        # byte-identical either way.
        timer = get_steplog().timer()
        try:
            if chaos_fire("stall_step"):
                # chaos drill for the stalled-step watchdog: sleep as if the
                # dispatch wedged — INSIDE the step's timer since ISSUE 52, so
                # that its sampler sees the sleep. On wake, a bumped epoch means
                # the watchdog already warm-restarted the world — this step
                # must vanish.
                time.sleep(float(os.environ.get("CHAOS_STALL_S", "2.0")))
                if epoch != self._epoch:
                    return None
            return self._step(timer, epoch)
        finally:
            timer.close()  # a step that raised or returned early

    def _admit_pending(self, timer, act: np.ndarray) -> tuple[int, int]:
        """The admission loop of a step: waiting requests into free slots,
        FIFO (or by ``tenancy.pick``), each fenced alone — deadline shed at
        dequeue, ``PoolExhausted`` requeued or shed, any other fault of its
        host half failed typed — and, where several wait beside as many free
        slots and the engine groups admissions (``admit_rows``), launched
        together: up to that many requests a ``_launch_group``. Returns
        (admitted, attempted) for the step ledger."""
        from ..utils import get_metrics
        from ..utils.chaos import chaos_fire

        m = get_metrics()
        plane = self.tenancy
        n_admitted = 0    # successful admissions (slot went live)
        n_attempted = 0   # dequeued attempts, failures/sheds included
        A = self.engine.admit_rows
        waiting: list[_Waiting] = []  # host halves done, launch to come

        def launch() -> None:
            nonlocal n_admitted
            self._launch_group(waiting, timer)
            for w in waiting:
                if self.slots[w.slot].request_id != w.rid:
                    continue  # the launch failed it (typed, in ``results``)
                act[w.slot] = True
                n_admitted += 1
                # chaos drill arming, as for a per-slot admission below
                if chaos_fire("nan_logits"):
                    self._nan_slots.add(w.slot)
                if chaos_fire("dead_fsm"):
                    self.fsm = self.fsm.at[w.slot].set(-1)
            waiting.clear()

        while self.pending:
            slot = self._free_slot(act)
            if slot is None:
                break
            if plane is None:
                rid, prompt = self.pending.pop(0)
            else:
                # weighted fair-share admission: smallest-vtime lane with
                # slot-cap headroom wins, FIFO within a lane (tenancy.pick)
                idx = plane.pick(
                    [self._tenant.get(r) for r, _ in self.pending])
                if idx is None:
                    break  # every waiter's lane is at its slot cap
                rid, prompt = self.pending.pop(idx)
            now = time.perf_counter()
            queue_ms = (now - self._enqueued_at.get(rid, now)) * 1e3
            n_attempted += 1
            dl = self._deadline.get(rid)
            if dl is not None and dl.expired:
                # satellite fix: admission shed expired deadlines before
                # ENQUEUE only — re-check at dequeue, where overload queue
                # time actually accumulates, so a stale request never
                # occupies a decode slot
                self.results[rid] = _err_result("shed: deadline expired in queue")
                m.inc("scheduler.shed_expired")
                if plane is not None:
                    plane.on_dequeue(self._tenant.get(rid), admitted=False)
                self._cleanup(rid)
                continue
            # a group is open while one waits for its launch, and opens when
            # another request waits behind this one beside another free slot
            grouped = A > 0 and (bool(waiting) or (
                bool(self.pending) and any(
                    not act[b] and self.slots[b].request_id < 0
                    for b in range(slot + 1, self.B))))
            try:
                how = self._admit(slot, rid, prompt, timer, queue_ms,
                                  waiting if grouped else None)
                self._pool_wait.pop(rid, None)
                self._requeues.pop(rid, None)
                if plane is not None:
                    plane.on_dequeue(self._tenant.get(rid), admitted=True)
                if how == "live":
                    act[slot] = True
                    n_admitted += 1
                    # chaos drill arming (no-ops with chaos off): NaN logits
                    # on this slot's next chunk / FSM state forced dead (a
                    # chunked admission arms at its final chunk instead)
                    if chaos_fire("nan_logits"):
                        self._nan_slots.add(slot)
                    if chaos_fire("dead_fsm"):
                        self.fsm = self.fsm.at[slot].set(-1)
                elif len(waiting) == A:
                    launch()
            except PoolExhausted as e:
                # pool-pressure degradation ladder (stage 3; stages 1-2 —
                # radix cold-leaf eviction and session-cache admission
                # denial — live in the paged engine): requeue at the head
                # while in-flight slots can still free blocks, shed with a
                # typed 503-mapped error once nothing can (no live slots)
                # or the wait/deadline budget is burned
                try:
                    self.engine.release_slot(slot, ok=False)
                except Exception:
                    pass  # partial admission state is best-effort cleanup
                first = self._pool_wait.setdefault(rid, time.perf_counter())
                waited = time.perf_counter() - first
                if ((not act.any() and not waiting) or waited >= self._pool_wait_s
                        or (dl is not None and dl.expired)):
                    self.results[rid] = _err_result(f"shed: {e}")
                    m.inc("scheduler.shed_pool")
                    if plane is not None:
                        plane.on_dequeue(self._tenant.get(rid), admitted=False)
                    self._cleanup(rid)
                else:
                    n_req = self._requeues.get(rid, 0) + 1
                    if n_req > self._requeue_max and self.pending:
                        # aging bound (ISSUE 18 satellite): an oversized
                        # prompt requeued at the head SCHED_REQUEUE_MAX
                        # times rotates to the back, so the small requests
                        # stuck behind it get their admission attempt
                        # instead of starving indefinitely
                        self._requeues[rid] = 0
                        self.pending.append((rid, prompt))
                        m.inc("scheduler.requeue_rotations")
                    else:
                        self._requeues[rid] = n_req
                        self.pending.insert(0, (rid, prompt))
                break  # stop admitting; let the live batch drain blocks
            except Exception as e:
                if isinstance(e, _DeviceFault):
                    # a device fault is never per-request: propagate rather
                    # than dispatch more chunks on a corrupted engine (the
                    # colocate loop fails inflights + the watchdog restarts)
                    raise
                # per-request prefill fence: oversized prompt (ValueError),
                # tokenizer fault, chaos injection — fails alone, never the
                # batch. Non-ValueError faults count as poison offenses so
                # a prompt that keeps exploding prefill gets quarantined.
                try:
                    self.engine.release_slot(slot, ok=False)
                except Exception:
                    pass
                self.results[rid] = _err_result(str(e))
                if not isinstance(e, ValueError):
                    m.inc("scheduler.prefill_faults")
                    self._record_offense(rid, f"prefill {type(e).__name__}")
                if plane is not None:
                    plane.on_dequeue(self._tenant.get(rid), admitted=False)
                self._cleanup(rid)

        if waiting:
            launch()
        return n_admitted, n_attempted

    def _step(self, timer, epoch: int) -> ChunkResult | None:
        from ..utils import get_metrics

        m = get_metrics()
        timer.stage("sched.admit")

        act = self._active_h  # host mirror — no device readback for admission
        # mid-decode cancellation: a slot whose deadline expired aborts at
        # the chunk boundary, releasing slot + blocks instead of burning
        # decode steps for a response nobody will read
        for b in range(self.B):
            rid = self.slots[b].request_id
            if rid >= 0:
                dl = self._deadline.get(rid)
                if dl is not None and dl.expired:
                    self._evict_slot(b, "cancelled: deadline expired mid-decode",
                                     "scheduler.cancelled")
        plane = self.tenancy
        if (plane is not None and self._preempt_on and self.pending
                and self._free_slot(act) is None):
            # over-budget preemption (ISSUE 18): all slots busy while a
            # poorer lane starves — vacate the richest lane's slot at this
            # chunk boundary (at most one per step; see _preempt_slot)
            victim = plane.over_budget_victim(
                [(b, self._tenant.get(self.slots[b].request_id))
                 for b in range(self.B)
                 if self.slots[b].request_id >= 0 and act[b]
                 and self.slots[b].token_ids
                 and self._preempted.get(self.slots[b].request_id, 0) < 1],
                [self._tenant.get(r) for r, _ in self.pending])
            if victim is not None:
                self._preempt_slot(victim)
        n_admitted, n_attempted = self._admit_pending(timer, act)

        # drop enqueue stamps with no pending entry left (requests admitted
        # above pop their own; these are abandons — colocate tombstoning
        # filters self.pending directly — which must not leak the dict)
        if len(self._enqueued_at) > len(self.pending):
            live = {r for r, _ in self.pending}
            for r in [r for r in self._enqueued_at if r not in live]:
                del self._enqueued_at[r]
                if plane is not None and r in self._tenant:
                    # colocate tombstoning filtered this rid out of pending
                    # directly — the lane's queued count must not leak
                    plane.on_dequeue(self._tenant.pop(r), admitted=False)
                    self._prompt_src.pop(r, None)

        # chunked admissions (ISSUE 19): one interleaved prefill chunk per
        # in-flight admission per step — the admit/prefill ledger stages
        # show the decode isolation directly (prefill time lands in the
        # prefill stage, never inside batch-mates' decode segment)
        adm_done, adm_stepped = self._advance_admissions(act, timer)
        n_admitted += adm_done
        n_attempted += adm_stepped

        if not act.any():
            if n_attempted:
                # admissions were attempted but every one failed/shed
                # (pool-exhaustion storm, expired deadlines, prefill
                # faults): still a step that spent wall time, during
                # exactly the overload churn an autopsy needs — record it
                timer.finish(occupancy=0, tokens=0, admitted=n_admitted)
            return None

        # the engine's layout-kernel calls were stage spans of their own
        # INSIDE the admission stage (``sched.admit.prefill``, what
        # ``prefill_ms`` times) and are reported as the prefill stage, so
        # admit is the queue, the bookkeeping and the rest of each
        # admission; ``*.prefill_call`` is a PART of one admission (the
        # jitted call alone), not a stage
        timer.stage("sched.decode_dispatch")
        eng = self.engine
        nan_inject = None  # the chaos drill's one-shot mask, THIS chunk's
        if self._nan_slots:
            nan_inject = np.zeros((self.B,), dtype=bool)
            nan_inject[list(self._nan_slots)] = True
            self._nan_slots.clear()
        t_chunk0 = time.perf_counter()
        occupancy = int(act.sum())  # slots riding THIS chunk's dispatches
        self._rng, k = jax.random.split(self._rng)
        # ``live``: few enough live rows ride a compacted program (ISSUE 29)
        res = eng.decode_chunk(
            self.cur, self.pos, self.fsm, self.active, self.nbytes,
            self.tokens_left, k, self.temperature, self.byte_budget,
            self.chunk_steps, self.greedy, live=act, nan_inject=nan_inject,
        )
        timer.stage("sched.readback")
        # one transfer for everything the host needs this chunk (a combined
        # device_get is ONE host<->device sync; separate gets pay one each;
        # a field the engine does not report is None, an empty leaf).
        # ``fwds`` keeps tokens-per-forward truthful under multi-token steps
        # (counting dispatches as tokens would inflate every throughput
        # gauge); ``poison`` is the quarantine's per-row fault codes below.
        (out_h, n_h, act_h, eos_h, pos_h, fwds_h, pois_h, conf_h, counts_h) = (
            jax.device_get((res.out, res.n, res.active, res.eos, res.pos, res.fwds,
                            res.poison, res.conf, res.counts)))
        out_h, n_h, act_h, eos_h, pos_h, pois_h = (
            np.asarray(x) for x in (out_h, n_h, act_h, eos_h, pos_h, pois_h))
        fwds_h, rows = int(fwds_h), res.rows
        timer.stage("sched.release")
        if epoch != self._epoch:
            # the watchdog warm-restarted the engine while this step was
            # stalled in flight: its world is gone — committing the chunk's
            # state would scribble stale arrays over the fresh one
            return None
        (self.cur, self.pos, self.fsm, self.active, self.nbytes,
         self.tokens_left) = (res.cur, res.pos, res.fsm, res.active,
                              res.nbytes, res.tokens_left)
        self._active_h = np.array(act_h)
        # paged engines clamp their block-growth targets to the actual
        # frontier (the ff worst-case claim must not compound per chunk)
        eng.reconcile_coverage(pos_h)

        # EMITTED tokens, never forward dispatches: `n` is the per-row
        # emitted count in every engine layout (plain, ff), so the tokens/s
        # EMA below stays truthful when one forward emits several tokens
        m.inc("scheduler.tokens_generated", float(n_h.sum()))
        m.inc("scheduler.chunks")
        if fwds_h > 0:
            m.inc("scheduler.forwards", float(fwds_h))
            # rows COMPUTED: forwards at the width this chunk was dispatched
            m.inc("scheduler.forward_rows", float(fwds_h) * rows)
            m.set_gauge("scheduler.tokens_per_forward",
                        float(n_h.sum()) / float(fwds_h))
        # what the chunk program counted, summed over its forwards (and layers):
        # each vector into the counters its model's record names, in the
        # forward's order (``models.family.Count.metrics``: the metric catalog's
        # lint reads the names there). Per forward they are these over
        # ``scheduler.forwards`` (docs/OBSERVABILITY.md)
        for name, values in counts_h.items():
            for metric, v in zip(eng.family.count(name).metrics, np.asarray(values), strict=True):
                m.inc(metric, float(v))
        if "ffn" not in counts_h and res.ffn_rows:  # a program that packs nothing: every forward whole
            m.inc("ffn.forwards_packed", 0.0)
            m.inc("ffn.rows", float(fwds_h) * res.ffn_rows)
        # saturation gauges: the signals continuous batching is tuned by —
        # backlog (queue_depth), batch occupancy (slots used / total), KV
        # page pressure (paged engines), and rolling throughput
        m.set_gauge("scheduler.queue_depth", len(self.pending))
        m.set_gauge("scheduler.active_slots", float(act_h.sum()))
        m.set_gauge("scheduler.batch_slots", float(self.B))
        m.set_gauge("scheduler.batch_occupancy", float(act_h.sum()) / self.B)
        chunk_s = time.perf_counter() - t_chunk0
        if chunk_s > 0:
            inst = float(n_h.sum()) / chunk_s
            self._tps_ema = inst if self._tps_ema == 0.0 \
                else 0.8 * self._tps_ema + 0.2 * inst
            m.set_gauge("scheduler.tokens_per_s", self._tps_ema)
        if eng.allocator is not None:
            from .paged import record_pool_gauges

            record_pool_gauges(eng.allocator, engine=eng)
        if eng.radix is not None:
            from .radix import record_radix_gauges

            record_radix_gauges(eng.radix)
        if plane is not None:
            # tenant.* occupancy/share/SLO gauges ride the TS rings and the
            # fleet plane automatically once set here (ISSUE 18)
            plane.export_gauges()
        # live HBM ledger tick (throttled to HBM_LEDGER_S inside — the
        # jax.live_arrays walk must not run per chunk); plan-vs-measured
        # drift is an alarm, never a serving fault
        try:
            from ..utils.hbmledger import record_hbm_gauges

            record_hbm_gauges(eng)
        except Exception:
            pass

        # ISSUE 15 conf lanes: per-row (margin_sum, margin_min, entropy_sum,
        # forced, decisions) folded into per-request accounting so finished
        # results carry an honest quality vector
        conf_arr = None if conf_h is None else [np.asarray(x) for x in conf_h]

        # cost fold (ISSUE 17): one per-row ledger dict per chunk, computed
        # from readbacks already paid for. A row pays one position per
        # emitted token (grammar fast-forward writes each forced token's KV
        # through the same per-position compute).
        # KV block-time: paged rows hold owned + shared blocks for the
        # chunk wall; dense rows hold 1 "block" (their whole KV line).
        costs = self.costs
        chunk_us = int(round(chunk_s * 1e6))
        chunk_flops = 0
        chunk_kv_bytes = 0

        for b in range(self.B):
            sl = self.slots[b]
            if sl.request_id < 0:
                continue
            if b in self._admitting:
                # mid-chunked-admission: the slot owns a request but its
                # device row is not active yet, so this chunk's readback
                # (act/n/eos/pos) carries junk for it — the "slot stopped"
                # branch below would release a request that never started
                # decoding. The admission loop owns this slot until its
                # final chunk lands.
                continue
            if plane is not None:
                # advance the lane's virtual-token clock by the row's
                # emitted tokens (tokens / weight — the fair-share currency)
                plane.charge(self._tenant.get(sl.request_id), int(n_h[b]))
            if costs is not None and sl.cost is not None:
                # fold BEFORE the poison branch: an evicted row's spent
                # chunk cost must ride out on its error result
                fl, by = costs.model.decode_row(int(n_h[b]), int(pos_h[b]))
                kv_us = chunk_us * eng.slot_block_count(b)
                sl.cost["decode_flops"] += fl
                sl.cost["decode_bytes"] += by
                sl.cost["kv_block_us"] += kv_us
                costs.fold_row({"decode_flops": fl, "decode_bytes": by,
                                "kv_block_us": kv_us})
                chunk_flops += fl
                chunk_kv_bytes += by
            if int(pois_h[b]) > 0:
                # poison-request quarantine: the loop fenced this row off
                # mid-chunk (non-finite logits / dead FSM state) without
                # touching batch-mates. Evict the slot with a typed error,
                # free its KV refs WITHOUT radix insertion, count the
                # offense against the prompt, and freeze a flight-recorder
                # dump — every contained incident leaves evidence.
                reason = ("non-finite logits" if int(pois_h[b]) == 1
                          else "grammar dead state")
                self._record_offense(sl.request_id, reason)
                self._evict_slot(b, f"poisoned: {reason}",
                                 "scheduler.slots_quarantined")
                from ..utils.tracing import get_flight_recorder

                get_flight_recorder().trigger("scheduler.quarantine",
                                              detail=reason)
                continue
            sl.token_ids.extend(int(t) for t in out_h[b, : n_h[b]])
            if conf_arr is not None:
                sl.conf_msum += float(conf_arr[0][b])
                sl.conf_mmin = min(sl.conf_mmin, float(conf_arr[1][b]))
                sl.conf_esum += float(conf_arr[2][b])
                sl.conf_forced += int(conf_arr[3][b])
                sl.conf_cnt += int(conf_arr[4][b])
            if not act_h[b]:
                # slot stopped this chunk: clean EOS, or truncation by
                # byte/token/length budget (eos flag distinguishes them)
                from ..utils.quality import conf_summary

                self.results[sl.request_id] = GenerationResult(
                    text=self.engine.tokenizer.decode(sl.token_ids),
                    token_ids=list(sl.token_ids),
                    prefill_ms=sl.prefill_ms,
                    # clamped: a request finishing inside timer resolution
                    # (short answer riding one multi-token chunk) must not
                    # report a negative duration
                    decode_ms=max(
                        0.0,
                        (time.perf_counter() - sl.start_s) * 1e3 - sl.prefill_ms),
                    steps=len(sl.token_ids),  # accepted tokens, not forwards
                    finished=bool(eos_h[b]),
                    cached_tokens=sl.cached_tokens,
                    prompt_tokens=sl.prompt_len,
                    queue_ms=sl.queue_ms,
                    quality=conf_summary(
                        (sl.conf_msum, sl.conf_mmin, sl.conf_esum,
                         sl.conf_forced, sl.conf_cnt), len(sl.token_ids)),
                    cost=dict(sl.cost) if sl.cost is not None else None,
                )
                m.inc("scheduler.requests_completed")
                m.observe_ms("scheduler.request_total",
                             (time.perf_counter() - sl.start_s) * 1e3)
                if plane is not None:
                    t = self._tenant.get(sl.request_id)
                    plane.on_release(t)
                    plane.fold_cost(t, sl.cost)
                    plane.observe_latency(
                        t, (time.perf_counter() - sl.start_s) * 1e3)
                self._cleanup(sl.request_id)
                self.slots[b] = _Slot()
                # paged engines free the blocks; with radix reuse on, the
                # generated ids let release insert the prompt+generated
                # chain back into the tree first
                self.engine.release_slot(b, generated_ids=sl.token_ids)

        # close the ledger entry: everything after the readback (commit,
        # release/radix-insert, gauge exports, HBM tick) is "release"
        # roofline reconciliation (ISSUE 17): the chunk's analytic FLOPs /
        # KV bytes against the measured chunk wall -> engine.mfu /
        # engine.mbu gauges + cost.* counters (weights stream per forward
        # dispatch, batch-shared, metered engine-side)
        if costs is not None:
            try:
                costs.chunk(chunk_flops, chunk_kv_bytes, fwds_h, chunk_s)
            except Exception:
                pass  # metering must never become a serving fault
        timer.finish(
            occupancy=occupancy,
            rows=rows,
            tokens=int(n_h.sum()),
            admitted=n_admitted or None,
            forwards=fwds_h,
        )
        return res

    # ------------------------------------------------------------ drain

    def run_until_done(self, max_chunks: int | None = None) -> None:
        if max_chunks is None:
            # worst case: every request decodes its full token budget
            import math

            per_req = math.ceil(self.max_new_tokens / self.chunk_steps) + 1
            if self._prefill_chunk:
                # a chunked admission spends up to ceil(max_len / C) steps
                # landing prefill chunks before its first decode chunk
                per_req += math.ceil(self.engine.max_len / self._prefill_chunk)
            if self.tenancy is not None:
                # a preempted request re-admits and may replay its full
                # budget once (one preemption per rid, _preempt_slot)
                per_req *= 2
            max_chunks = per_req * (len(self.pending) + self.B) + self.B
        for _ in range(max_chunks):
            if not self.pending and not any(s.request_id >= 0 for s in self.slots):
                break
            self.step()

    def generate_many(self, prompts: list[str]) -> list[GenerationResult]:
        ids = [self.submit(p) for p in prompts]
        self.run_until_done()
        return [
            self.results.pop(
                i,
                GenerationResult(
                    text="", token_ids=[], prefill_ms=0.0, decode_ms=0.0,
                    steps=0, finished=False, error="scheduler gave up (chunk cap)",
                ),
            )
            for i in ids
        ]
