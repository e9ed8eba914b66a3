"""Engine microscope (ISSUE 9): step ledger, recompilation sentinel, HBM
ledger, and the tooling that rides them.

The executable spec for the device-plane telemetry: the StepTimer's tiling
contract (stages account ≥95% of a real scheduler chunk's wall), the ring's
bounds and flight-recorder freeze integration, cache-miss compile detection
with the warmup fence (an induced post-fence recompile must surface as a
counter + a steplog event + a /health warning within one scrape), the
ledger-on/off token-identity differential, plan-vs-measured HBM
reconciliation, and the stepview/benchdiff tools (stepview --self-test
joins tier-1 here, alongside traceview's in test_observability).
"""

import json
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from tpu_voice_agent.serve import ContinuousBatcher, DecodeEngine
from tpu_voice_agent.utils import get_compile_watcher, get_metrics
from tpu_voice_agent.utils.compilewatch import CompileWatcher, _shape_sig, watch_compiles
from tpu_voice_agent.utils.hbmledger import (
    engine_hbm_plan,
    hbm_report,
    measure_hbm,
    record_hbm_gauges,
)
from tpu_voice_agent.utils.steplog import STAGES, StepLog, get_steplog
from tpu_voice_agent.utils.tracing import FlightRecorder

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tools"))

import benchdiff  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_global_telemetry():
    """Every test starts with an empty step ring and a disarmed, zeroed
    compile watcher — and leaves them that way (both are process-global;
    a leaked armed fence would tag other modules' compiles post-fence)."""
    get_steplog().clear()
    get_compile_watcher().reset()
    yield
    get_steplog().clear()
    get_compile_watcher().reset()


@pytest.fixture(scope="module")
def scope_engine():
    """Module-private engine with bucket/chunk shapes no other module uses,
    so its traces are cache-cold regardless of suite order (the sentinel
    counts jit-cache misses — a bucket another test already warmed would
    hide the induced compile)."""
    return DecodeEngine(preset="test-tiny", max_len=768, batch_slots=2,
                        prefill_buckets=(96, 192))


def _batcher(engine, **kw):
    kw.setdefault("chunk_steps", 7)
    kw.setdefault("max_new_tokens", 16)
    return ContinuousBatcher(engine, **kw)


# what every record holds since ISSUE 36, 0.0 where nothing happened
EVERY_RECORD = {"cpu_ms", "others_cpu_ms", "gap_ms", "gap_cpu_ms", "gap_others_cpu_ms",
                "lock_wait_ms", "gc_ms", "gc_max_ms", "gc_n", "watchdog_late_ms"}


def _spin(seconds: float) -> None:
    """Pure-Python work that holds the interpreter for ``seconds``."""
    import time

    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


# ------------------------------------------------------------ StepLog units


def test_steptimer_stages_tile_the_wall():
    import time

    log = StepLog(max_steps=8, enabled=True)
    t = log.timer()
    time.sleep(0.002)
    t.stage("sched.admit")
    time.sleep(0.005)
    t.stage("sched.decode_dispatch")
    time.sleep(0.002)
    t.stage("sched.readback")
    t.stage("sched.release")
    rec = t.finish(occupancy=2, tokens=5)
    assert rec["occupancy"] == 2 and rec["tokens"] == 5
    assert set(rec["stages"]) == {"admit", "decode", "readback", "release"}
    # the first stage runs from the step's start (the 2 ms before it was
    # opened are admit, as the first lap's were)
    assert rec["stages"]["admit"] >= 6.9 and rec["stages"]["decode"] >= 1.9
    # stage spans are contiguous segments of one perf_counter_ns stream:
    # they tile (each stage and the wall are rounded to 3 decimals
    # independently, so allow half-ulp rounding slack per recorded stage)
    slack = 5e-4 * (len(rec["stages"]) + 1)
    assert abs(sum(rec["stages"].values()) - rec["wall_ms"]) <= slack
    # the unrounded stamps bracket the wall on the wall clock
    assert rec["t0_ns"] < rec["t1_ns"]
    assert abs((rec["t1_ns"] - rec["t0_ns"]) / 1e6 - rec["wall_ms"]) < 1.0
    assert rec["t_s"] == round(rec["t1_ns"] / 1e9, 3)


def test_nested_stage_spans_are_taken_out_of_the_stage_around_them():
    """What ``carve`` did after the fact the spans do where it happens: a
    prefill call inside admit is the prefill stage and NOT admit — and the
    stages still tile the wall."""
    import time

    from tpu_voice_agent.utils.steplog import (
        ALLOC_SPAN,
        PREFILL_CALL_SPAN,
        PREFILL_STAGE_SPAN,
        REQUEST_SPAN,
        span,
    )

    log = StepLog(max_steps=8, enabled=True)
    t = log.timer()
    t.stage("sched.admit")
    for rid in (7, 8):
        with t.span(REQUEST_SPAN, rid=rid, queue_ms=1.5) as req:
            with span(f"{REQUEST_SPAN}.tokenize"):  # the engine's entry point
                time.sleep(0.001)
            req.set(prompt_tokens=12)
            with span(ALLOC_SPAN):  # the engine's prefill_slot
                time.sleep(0.001)
                with span(PREFILL_STAGE_SPAN), span(PREFILL_CALL_SPAN):
                    # 3 ms until PR 32: beside busy workers a 1 ms sleep ran 6 ms
                    # and "alloc < call" failed on the scheduler, not on the spans
                    time.sleep(0.015)
    time.sleep(0.002)
    t.stage("sched.decode_dispatch")
    time.sleep(0.001)
    t.stage("sched.release")
    rec = t.finish()
    st = rec["stages"]
    assert 29.9 <= st["prefill"] < 0.95 * (st["prefill"] + st["admit"])
    assert st["admit"] >= 3.9 and st["decode"] >= 0.9
    assert abs(sum(st.values()) - rec["wall_ms"]) <= 5e-4 * (len(st) + 1)
    # one ledger entry per request span, attributes and parts together
    assert [a["rid"] for a in rec["admissions"]] == [7, 8]
    a = rec["admissions"][0]
    assert a["queue_ms"] == 1.5 and a["prompt_tokens"] == 12
    assert a["tokenize_ms"] >= 0.9 and a["prefill_call_ms"] >= 14.9
    # a part inside another is taken out of it: alloc is not alloc + call
    assert 0.9 <= a["alloc_ms"] < a["prefill_call_ms"]
    parts = a["tokenize_ms"] + a["alloc_ms"] + a["prefill_call_ms"]
    assert 0.98 * a["request_ms"] <= parts <= a["request_ms"]
    # a request span that raises, or is dropped, is no admission
    t = log.timer()
    t.stage("sched.admit")
    with pytest.raises(RuntimeError):
        with t.span(REQUEST_SPAN, rid=9):
            raise RuntimeError("pool exhausted")
    with t.span(REQUEST_SPAN, rid=10) as req:
        req.drop()
    assert "admissions" not in t.finish()
    # outside a step the primitive is a bare annotation, and costs nothing
    with span(PREFILL_CALL_SPAN):
        pass


def test_cpu_clocks_tile_as_the_wall_does():
    """``cpu_ms`` and ``others_cpu_ms`` are read on the boundaries the wall is
    read on and tiled the same way: a nested staged span's CPU is its own
    stage's and is carved out of its parent ONCE, and a thread cannot burn more
    CPU than wall. Held as IDENTITIES and orders, with a tolerance that is a
    share of the step's own wall (ISSUE 52): how much CPU a spinning thread is
    GIVEN on a shared box is the box's to say — milliseconds against a constant
    went red in the driver's runs (ROADMAP D7)."""
    import time

    from tpu_voice_agent.utils.steplog import PREFILL_STAGE_SPAN, span

    log = StepLog(max_steps=8, enabled=True)
    own0 = time.thread_time_ns()
    t = log.timer()
    t.stage("sched.admit")
    _spin(0.02)
    with span(PREFILL_STAGE_SPAN):
        _spin(0.03)
    t.stage("sched.decode_dispatch")
    time.sleep(0.02)
    t.stage("sched.readback")
    _spin(0.01)
    rec = t.finish()
    own = (time.thread_time_ns() - own0) / 1e6  # what this thread burned around the whole step
    st, cpu, others = rec["stages"], rec["cpu_ms"], rec["others_cpu_ms"]
    wall = rec["wall_ms"]
    assert set(st) == set(cpu) == set(others) == {"admit", "prefill", "decode", "readback"}
    # the wall tiles exactly (rounding alone), whatever the machine did to the thread
    assert abs(sum(st.values()) - wall) <= 5e-4 * (len(st) + 1)
    # a spin never ends before its time, a sleep neither: the stages hold them
    assert st["admit"] >= 19.9 and st["prefill"] >= 29.9 and st["decode"] >= 19.9 and st["readback"] >= 9.9
    # a thread burns no more CPU than wall, in any stage and in all (the three
    # clocks are read in turn: a hundredth of the step between them)
    tol = 0.01 * wall
    assert sum(cpu.values()) <= wall + tol
    for k in st:
        assert cpu[k] <= st[k] + tol, (k, cpu, st)
    # carved ONCE: the nested span's CPU is ``prefill``'s and not also
    # ``admit``'s — counted twice, the stages would sum past what the thread
    # burned around the whole step (its timer's own work included), and a
    # stage left out would fall short of it by that stage
    assert sum(cpu.values()) <= own + tol
    assert sum(cpu.values()) >= own - tol - 0.5 * min(cpu["admit"], cpu["prefill"])
    # orders: the stage that slept burned the least, and a small share of its wall
    assert cpu["decode"] < min(cpu["admit"], cpu["prefill"]) and cpu["decode"] <= 0.25 * st["decode"]
    assert EVERY_RECORD <= set(rec)


@pytest.mark.parametrize("held_by", ["another_thread", "a_sleep", "its_own_work"])
def test_off_cpu_and_others_cpu_tell_three_causes_apart(held_by):
    """The discriminator of ISSUE 36 on a fake step of one host stage. Another
    thread busy in pure Python: the batcher's thread is off the CPU about as
    long as the others burn it. A sleep (the device, a lock): off the CPU,
    and nobody burns any. Its own busy loop: on the CPU."""
    import threading
    import time

    log = StepLog(max_steps=8, enabled=True)
    stop = threading.Event()

    def busy():  # pure Python: it holds the interpreter whenever it runs
        while not stop.is_set():
            pass

    other = threading.Thread(target=busy)
    if held_by == "another_thread":
        other.start()
    try:
        t = log.timer()
        t.stage("sched.admit")
        if held_by == "a_sleep":
            time.sleep(0.3)
        else:
            _spin(0.3)
        rec = t.finish()
    finally:
        stop.set()
        if other.is_alive():
            other.join(timeout=10)
    assert not other.is_alive()
    wall, cpu, others = rec["stages"]["admit"], rec["cpu_ms"]["admit"], rec["others_cpu_ms"]["admit"]
    # Time off the CPU that the MACHINE explains — the thread runnable and not
    # run (``run_delay_ms``, ISSUE 52) — is none of the three causes: on a
    # shared box a spinning thread is given what is left, and that went red as
    # "its own work off the CPU" in the driver's runs (ROADMAP D7). It is taken
    # off only where it can EXCUSE — the share that must be small, the loss
    # another thread's CPU must answer for — never off a share that must be
    # large (a thread woken for the interpreter waits for a CPU too)
    off = wall - cpu
    delay = rec.get("run_delay_ms", 0.0)
    if held_by == "another_thread":
        # two threads share one interpreter: each runs about half the time (a
        # loaded machine gives both less; the other thread's share stays a
        # third of what this one lost TO IT or more)
        assert off >= 0.2 * wall and others >= 0.3 * (off - delay), rec
    elif held_by == "a_sleep":
        assert off >= 0.9 * wall and others <= 0.15 * off, rec
    else:
        assert off - delay <= 0.35 * wall and others <= 0.35 * wall, rec


@pytest.mark.parametrize("where", ["another_thread", "own_thread", "the_gap"])
def test_a_collection_lands_in_the_step_it_fell_in(where):
    """``gc.callbacks`` → the event ring → the record of the step that closes
    next: a full collection from another thread inside a step is that step's
    with ``own`` false, one from the batcher's thread ``own`` true, one between
    two steps has ``at_ms`` < 0; the scalars count every collection."""
    import gc
    import threading

    from tpu_voice_agent.utils import steplog

    log = StepLog(max_steps=8, enabled=True)
    log.timer().finish()  # installs the callback; takes what the ring held
    assert steplog._on_gc in gc.callbacks
    if where == "the_gap":
        gc.collect()
    t = log.timer()
    t.stage("sched.admit")
    if where == "another_thread":
        th = threading.Thread(target=gc.collect)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()
    elif where == "own_thread":
        gc.collect()
    rec = t.finish()
    full = [g for g in rec["gc"] if g["gen"] == 2]
    assert len(full) == 1 and rec["gc_n"] >= 1
    (g,) = full
    assert g["own"] is (where != "another_thread")
    assert (g["at_ms"] < 0) is (where == "the_gap")
    assert 0 < g["ms"] <= rec["gc_max_ms"] <= rec["gc_ms"]
    if where != "the_gap":
        assert g["at_ms"] + g["ms"] <= rec["wall_ms"] + 0.5
    # counted for a scrape where the record is folded (never in the callback)
    assert get_metrics().counter_state()[0]["host.gc_collections"] >= 1
    assert "gc" not in log.timer().finish()  # taken once: the next record is quiet


def test_a_ledger_that_is_off_registers_no_gc_callback():
    import gc

    from tpu_voice_agent.utils import steplog

    had = steplog._on_gc in gc.callbacks
    if had:
        gc.callbacks.remove(steplog._on_gc)
    try:
        rec = StepLog(max_steps=8, enabled=False).timer().finish()
        assert steplog._on_gc not in gc.callbacks
        assert rec["gc_n"] == 0 and "gc" not in rec  # and it takes nothing off the ring
        StepLog(max_steps=8, enabled=True).timer().close()
        StepLog(max_steps=8, enabled=True).timer().close()
        assert gc.callbacks.count(steplog._on_gc) == 1  # ONE a process
    finally:
        if not had and steplog._on_gc in gc.callbacks:
            gc.callbacks.remove(steplog._on_gc)


@pytest.mark.parametrize("first_launch", ["per_slot", "group", "decode_dispatch"])
def test_the_head_of_a_step_closes_at_its_first_launch(first_launch):
    """``sched.admit.head``: from the admit stage's start to the first
    ``.prefill_call`` to ENTER — a per-slot admission's, a group's — and, in a
    step that admits nobody, to ``sched.decode_dispatch``; later launches do
    not move it."""
    import time

    from tpu_voice_agent.utils.steplog import (
        ALLOC_SPAN,
        HEAD_SPAN,
        PREFILL_CALL_SPAN,
        PREFILL_STAGE_SPAN,
        REQUEST_SPAN,
        span,
    )

    log = StepLog(max_steps=8, enabled=True)
    t = log.timer()
    t.stage("sched.admit")
    time.sleep(0.01)  # the head: tokenize, prepare, whoever else holds the thread
    assert t.open_spans() == ["sched.admit", HEAD_SPAN]
    if first_launch == "per_slot":
        for rid in (1, 2):
            with t.span(REQUEST_SPAN, rid=rid), span(ALLOC_SPAN):
                time.sleep(0.004)
                with span(PREFILL_STAGE_SPAN), span(PREFILL_CALL_SPAN):
                    assert HEAD_SPAN not in t.open_spans()
                    time.sleep(0.01)
    elif first_launch == "group":
        entries = []
        for rid in (1, 2):
            with t.span(REQUEST_SPAN, rid=rid) as req:
                time.sleep(0.002)
            entries.append(req.entry)
        with t.group(entries), span(ALLOC_SPAN):
            with span(PREFILL_STAGE_SPAN), span(PREFILL_CALL_SPAN):
                time.sleep(0.01)
    t.stage("sched.decode_dispatch")
    assert HEAD_SPAN not in t.open_spans()
    time.sleep(0.005)
    rec = t.finish(forwards=1)
    # a sleep never returns early: the head holds at least what was slept
    # before the first launch entered ...
    lo = {"per_slot": 13.9, "group": 13.9, "decode_dispatch": 9.9}[first_launch]
    assert lo <= rec["head_ms"], rec
    # ... and at most — by the ledger's OWN sums, a bound far under the
    # parent's ``lo + 8`` ms, which a loaded machine's longer sleeps failed one
    # run in three — the two stages less everything that follows the first
    # launch's entry: the launches themselves, the second request's host half,
    # the chunk. What is left over is entering and leaving the spans: 0.04-0.18
    # ms in 60 readings under load (4.9 once: a gap between two spans)
    adm = rec.get("admissions", [])
    later = sum(a["prefill_call_ms"] for a in adm)
    if first_launch == "per_slot":
        later += adm[1]["request_ms"] - adm[1]["prefill_call_ms"]
    stages = rec["stages"]["admit"] + rec["stages"].get("prefill", 0.0)
    assert rec["head_ms"] <= stages - later + 1e-3, rec
    assert rec["head_ms"] <= stages + 1e-3
    assert rec["head_cpu_ms"] <= rec["head_ms"] + 0.5 and rec["head_others_cpu_ms"] >= 0.0
    # a step that launched nothing (every admission shed) has no head
    t = log.timer()
    t.stage("sched.admit")
    assert "head_ms" not in t.finish()


def test_steplog_ring_bounds_and_seq():
    log = StepLog(max_steps=4, enabled=True)
    for _ in range(10):
        log.timer().finish()
    dump = log.dump()
    assert len(dump["steps"]) == 4
    assert dump["recorded"] == 10
    assert [s["seq"] for s in dump["steps"]] == [6, 7, 8, 9]
    assert log.last()["seq"] == 9
    assert len(log.steps(last=2)) == 2


def test_steplog_disabled_records_nothing():
    log = StepLog(max_steps=4, enabled=False)
    log.timer().finish()
    assert log.dump()["steps"] == [] and log.last() is None


def test_flight_freeze_carries_the_step_ring():
    log = get_steplog()
    log.timer().finish(occupancy=1, tokens=3)
    fr = FlightRecorder(max_traces=4)
    assert fr.trigger("test.freeze", detail="steplog ride-along")
    dump = fr.frozen_dump()
    assert dump["reason"] == "test.freeze"
    assert dump["steplog"]["steps"], "freeze must embed the step ring"
    assert dump["steplog"]["steps"][-1]["tokens"] == 3


# ------------------------------------------------- compile sentinel units


def test_watch_compiles_counts_cache_misses_once():
    w = get_compile_watcher()

    @watch_compiles("test.unit_fn")
    @jax.jit
    def f(x):
        return x * 2

    f(jnp.zeros((3,), jnp.float32))  # trace 1
    f(jnp.ones((3,), jnp.float32))   # cache hit — same shape
    f(jnp.zeros((5,), jnp.float32))  # trace 2 — new shape
    st = w.state()
    assert st["compiles"] == 2
    evs = w.events()
    assert [e["site"] for e in evs] == ["test.unit_fn", "test.unit_fn"]
    assert "float32[5]" in evs[-1]["shape"]
    assert st["post_fence_compiles"] == 0 and "warning" not in st


def test_fence_flags_post_fence_compiles_with_warning():
    w = get_compile_watcher()

    @watch_compiles("test.fence_fn")
    @jax.jit
    def g(x):
        return x + 1

    g(jnp.zeros((2,), jnp.float32))
    w.arm_fence("test warm")
    g(jnp.zeros((4,), jnp.float32))  # the post-fence retrace
    st = w.state()
    assert st["fence_armed"] and st["fence_reason"] == "test warm"
    assert st["post_fence_compiles"] == 1
    assert "recompile(s) after the warmup fence" in st["warning"]
    assert "test.fence_fn" in st["warning"]
    # the pending list hands the event to the step ledger exactly once
    pend = w.take_pending()
    assert len(pend) == 2 and pend[-1]["post_fence"]
    assert w.take_pending() == []


def test_shape_sig_compact_and_capped():
    sig = _shape_sig((jnp.zeros((2, 3), jnp.int32), {"a": 1}, [1, 2], 7), {})
    assert "int32[2,3]" in sig and "dict(1)" in sig and "seq(2)" in sig
    many = _shape_sig(tuple(jnp.zeros((i + 1,)) for i in range(10)), {})
    assert many.endswith("…")


# --------------------------------------------- the real scheduler plane


def test_ledger_accounts_chunk_wall_and_occupancy(scope_engine):
    bat = _batcher(scope_engine)
    res = bat.generate_many(["turn on the lights", "play some jazz"])
    assert all(r.error is None for r in res)
    steps = [s for s in get_steplog().steps() if s.get("occupancy")]
    assert steps, "decode chunks must land in the ring"
    for s in steps:
        acct = sum(s["stages"].values()) / s["wall_ms"]
        assert acct >= 0.95, f"only {acct:.1%} of step {s['seq']} accounted"
        assert set(s["stages"]) <= set(STAGES)
    # the per-chunk meta the HUD and stepview render
    assert steps[0]["occupancy"] >= 1
    assert sum(s.get("tokens", 0) for s in steps) >= sum(
        len(r.token_ids) for r in res)
    # engine.step.* metrics exported alongside
    snap = get_metrics().snapshot()
    assert snap["latency_ms"]["engine.step.wall"]["count"] >= len(steps)
    assert "engine.step.occupancy" in snap["gauges"]


def test_induced_post_fence_recompile_surfaces_everywhere(scope_engine):
    """The acceptance drill: warm the 96-bucket, declare serving warm, then
    submit a prompt that forces the cold 192-bucket — the sentinel counter,
    the step ledger's compile event, and the brain's /health warning must
    all fire within one scrape."""
    w = get_compile_watcher()
    bat = _batcher(scope_engine)
    assert all(r.error is None
               for r in bat.generate_many(["turn on the lights"]))
    w.take_pending()
    get_steplog().clear()
    before = w.state()["compiles"]

    w.arm_fence("warmup complete")
    ids = scope_engine.tokenizer.encode("turn on the lights and play jazz",
                                        bos=True)
    long_ids = (ids * ((120 // len(ids)) + 1))[:120]  # 96 < n <= 192
    bat.submit(list(long_ids))
    bat.run_until_done()

    # (1) the counter
    st = w.state()
    assert st["compiles"] > before
    assert st["post_fence_compiles"] >= 1
    assert "warning" in st
    # (2) the steplog event, on the step that paid the trace
    evs = [ev for s in get_steplog().steps() for ev in (s.get("events") or [])]
    assert any(ev["post_fence"] and "prefill" in ev["site"] for ev in evs), evs
    # (3) the /health warning, one scrape
    from tests.http_helper import AppServer
    from tpu_voice_agent.services.brain import RuleBasedParser, build_app

    import urllib.request

    with AppServer(build_app(RuleBasedParser())) as srv:
        with urllib.request.urlopen(srv.url + "/health", timeout=5) as r:
            body = json.loads(r.read().decode())
    cs = body["compile_sentinel"]
    assert cs["post_fence_compiles"] >= 1
    assert "recompile(s) after the warmup fence" in cs["warning"]
    assert body["last_step"]["stages"], "/health carries the last step"


def test_all_admissions_shed_still_records_a_step(scope_engine):
    """Overload churn — every dequeued admission sheds, nothing decodes —
    must still land in the ring: that admit/shed wall is exactly the time
    an overload autopsy needs accounted."""
    from tpu_voice_agent.utils.resilience import Deadline

    bat = _batcher(scope_engine)
    bat.submit("turn on the lights", deadline=Deadline(0.0))
    bat.step()
    assert bat.results, "expired request must shed at dequeue"
    steps = get_steplog().steps()
    assert steps, "the shed-only step must be recorded"
    assert steps[-1]["occupancy"] == 0 and steps[-1]["tokens"] == 0
    assert "admit" in steps[-1]["stages"]


def test_steplog_off_is_token_identical(scope_engine):
    log = get_steplog()
    bat_on = _batcher(scope_engine)
    on = bat_on.generate_many(["dim the bedroom lights", "what time is it"])
    log.enabled = False
    try:
        bat_off = _batcher(scope_engine)
        off = bat_off.generate_many(["dim the bedroom lights",
                                     "what time is it"])
    finally:
        log.enabled = True
    assert [r.token_ids for r in on] == [r.token_ids for r in off]
    assert all(r.error is None for r in on)
    # the ledger that was on holds ISSUE 36's keys on every record (zeros where
    # nothing happened), tiled as the stages are; the one that was off nothing
    steps = log.steps()
    assert steps and all(s["seq"] < len(steps) for s in steps)
    from tpu_voice_agent.utils import machine

    has = {k for k, there in machine.counters().sources().items() if there}
    for rec in steps:
        assert EVERY_RECORD <= set(rec), EVERY_RECORD - set(rec)
        # the machine's side (ISSUE 52): each counter the machine has, and no key
        # for one it lacks
        assert "stall_dump_n" not in rec and "stall" not in rec  # no watchdog, no sampler armed
        assert set(machine.MACHINE_KEYS) & set(rec) == has
        assert all(rec[k] is not None and rec[k] >= 0 for k in has)
        assert set(rec["cpu_ms"]) == set(rec["others_cpu_ms"]) == set(rec["stages"])
        assert sum(rec["cpu_ms"].values()) <= rec["wall_ms"] + 0.5
        if rec.get("forwards"):
            assert rec["head_ms"] <= rec["stages"]["admit"] + rec["stages"].get("prefill", 0.0) + 1e-3


def _assert_parts_tile(adm):
    """The parts of an admission sum to its ``sched.admit.request`` span to
    within 2 %, or to within what entering and leaving the spans themselves
    costs (on the CPU an admission of the tiny model is ~1.5 ms now that its
    tail is one launch) — for the admission that tiles BEST: work that no
    part times shows in EVERY admission, a descheduled gap on a loaded machine
    in some. Both are the ledger's own sums. What was read (three admissions a
    run, gap of request in ms, 12 runs beside this suite under six workers):
    the best 0.085-0.195 of 1.4-2.6, the median 0.097-0.259 of 1.4-20 (the
    parent's median-within-0.15 fails there one run in three), the worst
    0.18-4.3. 0.3 ms is 1.5 times the best admission's largest reading; the
    2 % is the parent's."""
    from tpu_voice_agent.utils.steplog import ADMISSION_PARTS

    gaps = []
    for a in adm:
        parts = sum(a.get(f"{p}_ms", 0.0) for p in ADMISSION_PARTS)
        assert parts <= a["request_ms"] + 1e-3, a
        gaps.append((a["request_ms"] - parts, a["request_ms"]))
    gap, whole = min(gaps)
    assert gap <= max(0.02 * whole, 0.3), gaps


def test_admissions_tile_their_request_and_count_admitted(scope_engine):
    """Each admission's parts (tokenize .. bookkeeping, the engine's
    ``.alloc`` and ``.prefill_call`` among them) tile its
    ``sched.admit.request`` span (``_assert_parts_tile``), one entry per admission,
    and the ``prefill`` stage keeps its meaning: what ``prefill_ms`` times,
    the layout kernel's whole call, of which the jitted call is a part."""
    from tpu_voice_agent.utils.steplog import ADMISSION_PARTS

    bat = _batcher(scope_engine)
    res = bat.generate_many(["turn on the lights", "play some jazz",
                             "what is the weather today"])
    assert all(r.error is None for r in res)
    steps = get_steplog().steps()
    adm = [a for s in steps for a in s.get("admissions", [])]
    assert len(adm) == 3 == sum(s.get("admitted", 0) for s in steps)
    for s in steps:
        assert len(s.get("admissions", [])) == s.get("admitted", 0)
        calls = sum(a["prefill_call_ms"] for a in s.get("admissions", []))
        assert calls <= s["stages"].get("prefill", 0.0) + 2e-3
    # ``prefill_ms`` is timed INSIDE the stage's span: the stage holds it, and
    # it is most of the stage (0.975-0.9999 of it in 12 runs beside this suite
    # under six workers, where "within 5 %" either way failed)
    # — of the stage the thread was RUN for: on a loaded box a thread taken off
    # the CPU between the span's clock and the engine's own stretched one
    # step's stage to three times its call (PR 52's runs), so the time the
    # machine kept the thread runnable and not run is taken off the stage
    # (``run_delay_ms``, the machine's own word for it: nothing where it has none)
    staged = sum(s["stages"].get("prefill", 0.0) for s in steps)
    kept_waiting = sum(s.get("run_delay_ms", 0.0) for s in steps if s.get("admissions"))
    assert 0.8 * (staged - kept_waiting) <= sum(r.prefill_ms for r in res) <= staged + 1e-3
    for a in adm:
        assert {f"{p}_ms" for p in ADMISSION_PARTS if p != "bookkeeping"} <= set(a)
        assert a["prompt_tokens"] > 0 and a["cached_tokens"] == 0 and a["rid"] >= 0
        assert a["head_ids_reused"] == 0  # no head was installed: every id was walked
    _assert_parts_tile(adm)
    # the result carries the request's own queue wait
    assert sorted(round(r.queue_ms, 3) for r in res) == sorted(
        round(a["queue_ms"], 3) for a in adm)


def test_a_group_span_shares_its_parts_out_over_its_members():
    """``StepTimer.group``: the launches several admissions share are ONE
    span; its parts and its own time land in every member's entry in even
    shares, the entries gain ``rows``, and a group that raises takes its
    members' entries out of the ledger (nobody was admitted)."""
    import time

    from tpu_voice_agent.utils.steplog import (
        ALLOC_SPAN,
        PREFILL_CALL_SPAN,
        PREFILL_STAGE_SPAN,
        REQUEST_SPAN,
        span,
    )

    log = StepLog(max_steps=8, enabled=True)
    t = log.timer()
    t.stage("sched.admit")
    entries = []
    for rid in (1, 2, 3):
        with t.span(REQUEST_SPAN, rid=rid, queue_ms=0.5) as req:
            with span(ALLOC_SPAN):  # the host half: ``prepare_admission``
                time.sleep(0.002)
        entries.append(req.entry)
    host = [dict(e) for e in entries]
    with t.group(entries):
        with span(ALLOC_SPAN):
            time.sleep(0.003)
            with span(PREFILL_STAGE_SPAN), span(PREFILL_CALL_SPAN):
                time.sleep(0.015)
        with span(f"{REQUEST_SPAN}.slot_state"):
            time.sleep(0.003)
    t.stage("sched.decode_dispatch")
    rec = t.finish()
    assert [a["rid"] for a in rec["admissions"]] == [1, 2, 3]
    for a, h in zip(rec["admissions"], host):
        assert a["rows"] == 3 and "rows" not in h
        assert a["prefill_call_ms"] >= 4.9 and a["slot_state_ms"] >= 0.9
        assert a["alloc_ms"] >= h["alloc_ms"] + 0.9  # its own host half + a third of the group's
        assert a["request_ms"] >= h["request_ms"] + 6.9
        parts = sum(a.get(f"{p}_ms", 0.0) for p in ("alloc", "prefill_call", "slot_state"))
        assert 0.95 * a["request_ms"] <= parts <= a["request_ms"] + 1e-3
    # the entries sum to the admit and prefill stages (the loop around them is the rest)
    whole = sum(a["request_ms"] for a in rec["admissions"])
    assert whole <= rec["stages"]["admit"] + rec["stages"]["prefill"] + 1e-3
    assert whole >= 0.9 * (rec["stages"]["admit"] + rec["stages"]["prefill"])
    assert rec["stages"]["prefill"] >= 14.9  # the one call's stage, once
    # a launch that raises admitted nobody
    t = log.timer()
    t.stage("sched.admit")
    with t.span(REQUEST_SPAN, rid=9) as req:
        pass
    with t.span(REQUEST_SPAN, rid=10) as other:
        pass
    with pytest.raises(RuntimeError):
        with t.group([req.entry]):
            raise RuntimeError("the device half failed")
    assert [a["rid"] for a in t.finish()["admissions"]] == [10] and other.entry["rid"] == 10


@pytest.mark.parametrize("n", [3, 5])
def test_a_groups_ledger_entries_carry_rows_and_sum_to_the_admit_stage(n):
    """Behind the batcher: n requests admitted in one step — one grouped call
    of 3, or one of 4 and a lone one — leave n entries with ``rows`` (members
    of their call), the six parts less ``first_token_call`` (a group's head
    runs on the last position alone), equal shares of the call within a
    group, and together the step's admit and prefill stages."""
    from tpu_voice_agent.serve.paged import PagedDecodeEngine
    from tpu_voice_agent.services.brain import install_prompt_prefix
    from tpu_voice_agent.services.prompts import render_prompt

    eng = PagedDecodeEngine(preset="test-tiny", max_len=1536, batch_slots=32, block_size=128,
                            pool_blocks=96, prefill_buckets=(128, 256, 1024), radix_enable=False)
    install_prompt_prefix(eng)
    bat = _batcher(eng)
    bat.warmup()
    get_steplog().clear()
    texts = ["go back", "scroll down", "play some jazz", "what time is it", "stop"][:n]
    for t in texts:
        bat.submit(render_prompt(t, {}))
    bat.step()
    (rec,) = get_steplog().steps()
    adm = rec["admissions"]
    assert rec["admitted"] == n == len(adm)
    assert [a["rows"] for a in adm] == ([3, 3, 3] if n == 3 else [4, 4, 4, 4, 1])
    grouped = [a for a in adm if a["rows"] > 1]
    for a in grouped:
        assert {"tokenize_ms", "alloc_ms", "prefill_call_ms", "slot_state_ms", "bookkeeping_ms",
                "queue_ms", "request_ms", "prompt_tokens"} <= set(a)
        assert a["cached_tokens"] == 879 and "first_token_call_ms" not in a
    assert len({a["prefill_call_ms"] for a in grouped}) == 1  # even shares of ONE call
    from tpu_voice_agent.utils.steplog import ADMISSION_PARTS

    for a in adm:  # the parts never pass the request (how closely they tile it: the unit test above)
        assert sum(a.get(f"{p}_ms", 0.0) for p in ADMISSION_PARTS) <= a["request_ms"] + 1e-3, a
    # by the ledger's own sums: the entries lie inside the two stages and are
    # most of them — the rest is the loop around them and, on a busy machine,
    # whatever gap fell between two spans: 0.94-0.97 of the stages alone,
    # 0.66-0.99 in 48 readings beside this suite under six workers and under
    # twelve spinning processes, where the parent's one half failed one run in
    # three under the driver's; a quarter keeps the entries from going missing.
    # The shares of the one or two calls lie inside the prefill stage
    whole, stages = sum(a["request_ms"] for a in adm), rec["stages"]["admit"] + rec["stages"]["prefill"]
    assert 0.25 * stages <= whole <= stages + 1e-3
    assert 0.0 < sum(a["prefill_call_ms"] for a in adm) <= rec["stages"]["prefill"] + 1e-3
    bat.reset()


def test_queue_wait_grows_when_slots_are_busy(scope_engine):
    """``queue_ms`` is submit() -> popped from ``pending``: ~0 on an idle
    batcher, a whole generation long for the requests that found both
    slots taken; the histogram ``scheduler.queue_wait`` holds the same
    waits the results and the ledger carry (its running sum and count are
    what ``/metrics`` exports as ``_sum`` / ``_count``)."""
    m = get_metrics()
    s0, n0 = m.counter_state()[1].get("scheduler.queue_wait", (0.0, 0))
    bat = _batcher(scope_engine)  # two slots
    import time

    t_submit = time.time_ns()
    res = bat.generate_many(["turn on the lights", "play some jazz",
                             "dim the bedroom lights", "what time is it"])
    assert all(r.error is None for r in res)
    waits = [r.queue_ms for r in res]
    # orders and counts, no wait against a constant (ISSUE 52; a loaded box's
    # first admission alone outlasted the 50 ms this held the second to): the
    # first two are admitted by the FIRST step, in order ...
    steps = get_steplog().steps()
    by_step = [[round(a["queue_ms"], 3) for a in s.get("admissions", [])] for s in steps]
    assert by_step[0] == [round(w, 3) for w in waits[:2]]
    # ... within it: neither waited longer than from its submit to that step's end
    assert max(waits[:2]) <= steps[0]["wall_ms"] + (steps[0]["t0_ns"] - t_submit) / 1e6
    # the last two wait for a slot, in later steps: at least one whole decode chunk
    assert sorted(w for ws in by_step[1:] for w in ws) == sorted(round(w, 3) for w in waits[2:])
    chunk_ms = min(s["wall_ms"] for s in steps if s.get("tokens"))
    assert min(waits[2:]) > max(waits[:2]) and min(waits[2:]) >= 0.5 * chunk_ms
    s1, n1 = m.counter_state()[1]["scheduler.queue_wait"]
    assert n1 - n0 == 4 and s1 - s0 == pytest.approx(sum(waits), rel=1e-6)
    assert "scheduler.admissions" not in m.counter_state()[0]  # one copy, not two
    # an idle batcher admits at once: in the first step it runs, behind nobody
    get_steplog().clear()
    bat2 = _batcher(scope_engine)
    (r,) = bat2.generate_many(["stop"])
    first = get_steplog().steps()[0]
    assert [round(a["queue_ms"], 3) for a in first["admissions"]] == [round(r.queue_ms, 3)]
    assert r.queue_ms < min(waits[2:])  # no slot to wait for


def test_profiler_capture_holds_the_step_and_its_admissions(scope_engine, tmp_path):
    """A CPU ``jax.profiler`` capture of three requests' steps: ``sched.step`` on the
    host plane with ``sched.admit.request`` and its parts nested inside,
    on the clock the device's operations are on."""
    from jax.profiler import ProfileData

    bat = _batcher(scope_engine)
    bat.generate_many(["stop"])  # compiled before the capture
    get_steplog().clear()
    for p in ["turn on the lights", "play some jazz", "what time is it"]:
        bat.submit(p)
    jax.profiler.start_trace(str(tmp_path))
    try:
        bat.run_until_done()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                           dict(ev.stats)) for ev in line.events
                          if ev.name.startswith("sched.")]
    steps = [s for s in spans if s[0] == "sched.step"]
    assert len(steps) >= 3
    assert [s[3]["step_num"] for s in steps] == [r["seq"] for r in get_steplog().steps()]
    reqs = [s for s in spans if s[0] == "sched.admit.request"]
    assert len(reqs) == 3  # two slots: two admissions, then the third
    inside = lambda a, b: b[1] <= a[1] and a[2] <= b[2]
    for r in reqs:
        assert sum(inside(r, s) for s in steps) == 1
        assert {"rid", "queue_ms", "prompt_tokens", "cached_tokens"} <= set(r[3])
        kids = {s[0].rsplit(".", 1)[1] for s in spans
                if s[0].startswith("sched.admit.request.") and inside(s, r)}
        assert {"tokenize", "alloc", "prefill_call", "first_token_call",
                "slot_state", "bookkeeping"} <= kids
    names = {s[0] for s in spans}
    assert {"sched.admit", "sched.decode_dispatch", "sched.readback",
            "sched.release"} <= names


def test_admission_tail_is_one_launch_and_compiles_once(scope_engine, tmp_path):
    """A warm one-shot admission under a CPU ``jax.profiler`` capture: the
    ``.slot_state`` part holds exactly ONE executable launch, the jitted
    ``_first_token_into_slot``, and no eager one-op program (the eager tail
    it replaced launched 36 there). The counter is the runtime's own
    ``…Executable::Execute`` event on the dispatching thread, which sees
    every program, pjit's fast path included — shown by the last-row
    slice's eager programs it counts under ``.first_token_call``. After
    ``warmup()`` an admission at another slot and another length compiles
    nothing, and its ledger entry still holds the six parts (that they tile
    the request: ``test_admissions_tile_their_request_and_count_admitted``)."""
    from jax.profiler import ProfileData

    from tpu_voice_agent.utils.steplog import ADMISSION_PARTS

    bat = _batcher(scope_engine)
    bat.warmup()
    bat.submit("stop")
    jax.profiler.start_trace(str(tmp_path))
    try:
        bat.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    launches, jitted, kids = {}, {}, set()
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events]
            reqs = [e for e in evs if e[0] == "sched.admit.request"]
            if not reqs:
                continue
            (req,) = reqs
            for part in (e for e in evs if e[0].startswith(req[0] + ".")
                         and req[1] <= e[1] and e[2] <= req[2]):
                name = part[0].rsplit(".", 1)[1]
                kids.add(name)
                inside = [e[0] for e in evs if part[1] <= e[1] and e[2] <= part[2]]
                launches[name] = launches.get(name, 0) + sum(
                    n.endswith("Executable::Execute") for n in inside)
                jitted.setdefault(name, set()).update(
                    n for n in inside if n.startswith("PjitFunction("))
    assert kids == set(ADMISSION_PARTS)
    assert launches["first_token_call"] >= 2, launches  # the hook sees eager programs
    assert launches["slot_state"] == 1, launches
    assert jitted["slot_state"] == {"PjitFunction(_first_token_into_slot)"}

    # slot 0 is live: the next admission lands in slot 1, in the other bucket
    assert bat._active_h[0] and not bat._active_h[1]
    watched = get_compile_watcher().state()["compiles"]
    compiled = []
    listener = lambda ev, _d, **_kw: compiled.append(ev) if ev.endswith(
        "backend_compile_duration") else None
    jax.monitoring.register_event_duration_secs_listener(listener)
    get_steplog().clear()
    try:
        bat.submit("open the settings page, " * 12 + "then turn on dark mode")
        bat.step()
    finally:
        jax._src.monitoring.unregister_event_duration_listener(listener)
    assert bat.slots[1].prompt_len > 96 and bat._active_h[1]
    assert not compiled and get_compile_watcher().state()["compiles"] == watched
    (entry,) = [a for s in get_steplog().steps() for a in s.get("admissions", [])]
    assert {f"{p}_ms" for p in ADMISSION_PARTS} <= set(entry)
    assert sum(entry[f"{p}_ms"] for p in ADMISSION_PARTS) <= entry["request_ms"] + 1e-3


def _hlo_shape(text: str):
    """(instruction count, the fusions' result types in order) of an
    optimised HLO module, metadata left out."""
    import re

    instr = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", text, re.M)
    return len(instr), [t for t, op in instr if op == "fusion"]


def test_the_compile_cache_keys_on_names_and_not_on_paths(monkeypatch):
    """Scope names are metadata, which JAX leaves out of the persistent
    cache's key by default: an executable written by another commit would
    come back with that commit's names, or none. ``place_compile_cache``
    puts metadata into the key and takes the Python tracebacks out of it,
    so what a lowered module carries besides the computation is its names
    and no file path or line (two checkouts share a cache, two
    vocabularies do not)."""
    from tpu_voice_agent.utils.compilecache import place_compile_cache

    names = ("jax_compilation_cache_include_metadata_in_key", "jax_traceback_in_locations_limit")
    keep = {k: getattr(jax.config, k) for k in names}

    def lowered():
        def f(x):
            with jax.named_scope("layer/ffn"):
                return jnp.sin(x) @ x
        return jax.jit(f).lower(jnp.ones((8, 8))).as_text(debug_info=True)

    try:
        monkeypatch.delenv("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", raising=False)
        place_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        text = lowered()
        assert "layer/ffn/dot_general" in text and ".py" not in text
        # an operator who asks JAX for source lines keeps them
        jax.config.update("jax_traceback_in_locations_limit", 10)
        monkeypatch.setenv("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "10")
        place_compile_cache()
        assert "test_steplog.py" in lowered()
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)


@pytest.mark.parametrize("program", ["forward_paged", "paged_chunk_decode_loop"])
def test_named_scopes_change_metadata_only(program, monkeypatch):
    """Scopes name ops; they must not add one. The optimised HLO of the
    prefill forward and of the paged chunk loop has the same instruction
    count and the same fusions with ``jax.named_scope`` switched off."""
    import contextlib

    from tpu_voice_agent.models.llama import forward_paged
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.serve.paged import paged_chunk_decode_loop

    eng = PagedDecodeEngine(preset="test-tiny", max_len=256, batch_slots=2,
                            block_size=32, prefill_buckets=(32,), fast_forward=4)
    B = eng.batch_slots
    z = jnp.zeros((B,), jnp.int32)

    def compiled() -> str:
        jax.clear_caches()
        if program == "forward_paged":
            low = forward_paged.__wrapped__.lower(  # under watch_compiles
                eng.params, eng.cfg, jnp.zeros((1, 32), jnp.int32),
                jnp.arange(32, dtype=jnp.int32)[None] + 64, eng.k_pool, eng.v_pool,
                eng.block_tables[0][None], attn_impl="xla", fresh_block=False,
                gather_blocks=4)
        else:
            low = paged_chunk_decode_loop.__wrapped__.lower(
                eng.params, eng.cfg, eng.k_pool, eng.v_pool, eng.block_tables,
                z, z, z, jnp.ones((B,), bool), z, z + 8, eng.tables_ff,
                eng.byte_len_table, jax.random.PRNGKey(0), jnp.float32(0.7),
                jnp.int32(1000), trash_idx=eng._trash_idx,
                logit_mask=eng.logit_mask, chunk_steps=4, kernels=eng.kernels,
                eos_id=eng.eos_id, pad_id=eng.pad_id, max_len=eng.max_len)
        return low.compile().as_text()

    # the persistent compile cache keys on the computation and not on its
    # metadata: left on, the second compile below could be served the first
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        scoped = compiled()
        monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = compiled()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
        jax.clear_caches()
    assert "layer/ffn" in scoped and "lm_head" in scoped
    if program == "paged_chunk_decode_loop":
        assert "grammar_mask_sample" in scoped and "loop_carry" in scoped
    assert "layer/ffn" not in bare
    assert _hlo_shape(scoped) == _hlo_shape(bare)


def _pallas_calls():
    import ast

    out = []
    for path in sorted((ROOT / "tpu_voice_agent" / "ops").glob("*.py")):
        tree = ast.parse(path.read_text())
        for fn in tree.body:
            if not isinstance(fn, ast.FunctionDef):
                continue
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "pallas_call"):
                    name = next((kw.value for kw in node.keywords if kw.arg == "name"), None)
                    out.append(pytest.param(fn.name, name,
                                            id=f"{path.stem}.{fn.name}"))
    return out


@pytest.mark.parametrize("entry_point,name", _pallas_calls())
def test_every_pallas_call_is_named_after_its_entry_point(entry_point, name):
    """The trace shows a custom call under its kernel's ``name``: each is
    the Python function a reader of the trace would grep for."""
    import ast

    assert isinstance(name, ast.Constant) and name.value == entry_point


def test_warm_restart_rearms_the_fence(scope_engine):
    w = get_compile_watcher()
    assert not w.fence_armed
    scope_engine.warm_restart()
    assert w.fence_armed
    assert w.state()["fence_reason"] == "warm_restart"


# ------------------------------------------------------------ HBM ledger


def test_hbm_plan_matches_measured_weights_and_kv(scope_engine):
    plan = engine_hbm_plan(scope_engine)
    meas = measure_hbm(scope_engine)
    # the plan is config arithmetic, the measurement sums real nbytes —
    # they must agree on the parts both account (dense engine: exact)
    assert meas["weights_bytes"] == plan["weights_bytes"]
    assert meas["kv_pool_bytes"] == plan["kv_pool_bytes"]
    rep = hbm_report(scope_engine)
    assert abs(rep["drift"]) < 0.02
    assert rep["plan"]["total_bytes"] > 0


def test_hbm_gauges_exported_and_throttled(scope_engine):
    rep = record_hbm_gauges(scope_engine, force=True)
    assert rep is not None
    g = get_metrics().gauges()
    for name in ("hbm.weights_bytes", "hbm.kv_pool_bytes",
                 "hbm.plan_total_bytes", "hbm.plan_drift"):
        assert name in g, name
    assert g["hbm.weights_bytes"] == rep["measured"]["weights_bytes"]
    # throttle: an immediate second call inside the interval is a no-op
    assert record_hbm_gauges(scope_engine, min_interval_s=60.0) is None


# ------------------------------------------------------------ tools


def test_stepview_self_test_passes():
    proc = subprocess.run([sys.executable, str(ROOT / "tools" / "stepview.py"),
                           "--self-test"], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "stepview self-test ok" in proc.stdout


def test_stepview_renders_real_ring(scope_engine, tmp_path):
    import stepview

    bat = _batcher(scope_engine)
    bat.generate_many(["turn on the lights"])
    body = get_steplog().dump()
    txt = stepview.render_timeline(body, width=32)
    assert "step ledger:" in txt and "█" in txt
    # flight-dump unwrap: stepview reads the frozen ``steplog`` section
    p = tmp_path / "flight.json"
    p.write_text(json.dumps({"frozen": True, "steplog": body}))
    assert stepview.load_dump(str(p))["recorded"] == body["recorded"]


def _runall_artifact(path, rows):
    path.write_text(json.dumps({
        "quick": True,
        "benches": {"bench_x.py": {"status": "ok", "rows": rows}},
    }))


def test_benchdiff_flags_directional_regressions(tmp_path):
    prev = tmp_path / "BENCH_runall_1.json"
    cur = tmp_path / "BENCH_runall_2.json"
    _runall_artifact(prev, [
        {"metric": "x_p50", "value": 100.0, "unit": "ms"},
        {"metric": "x_tps", "value": 50.0, "unit": "tokens/s"},
        {"metric": "x_count", "value": 3, "unit": "count"},
    ])
    _runall_artifact(cur, [
        {"metric": "x_p50", "value": 125.0, "unit": "ms"},        # +25% BAD
        {"metric": "x_tps", "value": 40.0, "unit": "tokens/s"},   # -20% BAD
        {"metric": "x_count", "value": 30, "unit": "count"},      # not gated
    ])
    regs, changes = benchdiff.diff_rows(benchdiff.load_rows(cur),
                                        benchdiff.load_rows(prev), 0.10)
    assert {r["metric"] for r in regs} == {"x_p50", "x_tps"}
    assert {c["metric"] for c in changes} == {"x_p50", "x_tps", "x_count"}
    # improvements are "moved", never regressions
    _runall_artifact(cur, [{"metric": "x_p50", "value": 50.0, "unit": "ms"}])
    regs, changes = benchdiff.diff_rows(benchdiff.load_rows(cur),
                                        benchdiff.load_rows(prev), 0.10)
    assert regs == [] and len(changes) == 1


def test_benchdiff_never_diffs_quick_against_full(tmp_path):
    """--quick runs trim workloads (capacity caps, token budgets): a quick
    artifact diffed against a full one reads as a huge phantom regression.
    pick_artifacts matches the table kind."""
    full_old = tmp_path / "BENCH_runall_20200101_000000.json"
    full_old.write_text(json.dumps({"benches": {}}))
    quick_old = tmp_path / "BENCH_runall_20200102_000000.json"
    quick_old.write_text(json.dumps({"quick": True, "benches": {}}))
    quick_new = tmp_path / "BENCH_runall_20200103_000000.json"
    quick_new.write_text(json.dumps({"quick": True, "benches": {}}))
    cur, prev = benchdiff.pick_artifacts(tmp_path)
    assert (cur, prev) == (quick_new, quick_old)
    # a full run skips the newer quick artifact back to the last full one
    full_new = tmp_path / "BENCH_runall_20200104_000000.json"
    full_new.write_text(json.dumps({"benches": {}}))
    cur, prev = benchdiff.pick_artifacts(tmp_path)
    assert (cur, prev) == (full_new, full_old)
    # no same-kind predecessor: the trajectory starts, nothing to gate
    quick_old.unlink()
    quick_new.unlink()
    full_old.unlink()
    assert benchdiff.pick_artifacts(tmp_path) == (full_new, None)


def test_benchdiff_gate_exit_codes(tmp_path):
    prev = tmp_path / "BENCH_runall_20200101_000000.json"
    cur = tmp_path / "BENCH_runall_20200102_000000.json"
    _runall_artifact(prev, [{"metric": "y_p50", "value": 100.0, "unit": "ms"}])
    _runall_artifact(cur, [{"metric": "y_p50", "value": 200.0, "unit": "ms"}])
    assert benchdiff.main(["--artifacts", str(tmp_path), "--gate"]) == 1
    # without --gate the diff reports but never fails the caller
    assert benchdiff.main(["--artifacts", str(tmp_path)]) == 0
    # tolerance raised past the move: clean
    assert benchdiff.main(["--artifacts", str(tmp_path), "--gate",
                           "--tolerance", "1.5"]) == 0
    # single artifact: the trajectory starts, no gate to fail
    cur.unlink()
    assert benchdiff.main(["--artifacts", str(tmp_path), "--gate"]) == 0


# ------------------------------------------------------------ services


def test_voice_health_forwards_brain_engine_microscope():
    from tests.http_helper import AppServer
    from tpu_voice_agent.serve.stt import NullSTT
    from tpu_voice_agent.services.brain import RuleBasedParser
    from tpu_voice_agent.services.brain import build_app as build_brain
    from tpu_voice_agent.services.voice import VoiceConfig
    from tpu_voice_agent.services.voice import build_app as build_voice

    import urllib.request

    get_compile_watcher().arm_fence("test")
    get_steplog().timer().finish(occupancy=1, tokens=2)
    with AppServer(build_brain(RuleBasedParser())) as brain:
        cfg = VoiceConfig(brain_url=brain.url, executor_url="http://127.0.0.1:1",
                          stt_factory=lambda: NullSTT())
        with AppServer(build_voice(cfg)) as voice:
            with urllib.request.urlopen(voice.url + "/health", timeout=5) as r:
                body = json.loads(r.read().decode())
    fwd = body["brain"]
    assert fwd["compile_sentinel"]["fence_armed"]
    assert fwd["last_step"]["tokens"] == 2


def test_brain_debug_steplog_endpoint():
    from tests.http_helper import AppServer
    from tpu_voice_agent.services.brain import RuleBasedParser, build_app

    import urllib.request

    log = get_steplog()
    for i in range(5):
        log.timer().finish(occupancy=i, tokens=i)
    with AppServer(build_app(RuleBasedParser())) as srv:
        with urllib.request.urlopen(srv.url + "/debug/steplog?last=2",
                                    timeout=5) as r:
            body = json.loads(r.read().decode())
    assert body["service"] == "brain"
    assert len(body["steps"]) == 2 and body["recorded"] == 5
    assert body["steps"][-1]["occupancy"] == 4


# ---------------------------------------------------- what holds the batcher's thread (ISSUE 36)


def test_gap_and_lock_wait_of_a_step_behind_a_held_lock(scope_engine):
    """The stretch between two steps is the NEXT record's ``gap_ms``, and what
    the serving loop waited for its own lock in it ``lock_wait_ms``: a thread
    that holds ``ColocatedServing._lock`` for 60 ms between two ticks shows
    in both, and in neither stage of either step."""
    import threading
    import time

    from tpu_voice_agent.serve.colocate import ColocatedServing

    log = get_steplog()
    co = ColocatedServing(None, _batcher(scope_engine, max_new_tokens=64))
    co.submit_parse("sort by price low to high")
    assert co.step()
    n0 = len(log.steps())
    first = log.steps()[-1]
    assert first["lock_wait_ms"] < 30.0
    held, go = threading.Event(), threading.Event()

    def hold():
        with co._lock:
            held.set()
            go.wait(timeout=30)
            time.sleep(0.06)

    th = threading.Thread(target=hold)
    th.start()
    assert held.wait(timeout=30)
    go.set()
    assert co.step()  # its first acquisition waits out the holder
    th.join(timeout=30)
    assert not th.is_alive()
    rec = log.steps()[n0]
    assert rec["seq"] == first["seq"] + 1
    assert rec["lock_wait_ms"] >= 55.0 and rec["gap_ms"] >= rec["lock_wait_ms"]
    assert rec["gap_cpu_ms"] <= rec["gap_ms"] - 50.0  # it slept on the lock
    assert sum(rec["stages"].values()) == pytest.approx(rec["wall_ms"], abs=0.01)
    co.drain(timeout_s=300)
    assert all(s["lock_wait_ms"] < 30.0 for s in log.steps()[n0 + 1:])


def test_a_long_step_is_photographed_once_and_nothing_restarts(scope_engine, monkeypatch):
    """The chaos drill ``stall_step`` (2 s of sleep at the top of a step) under
    the watchdog at its default threshold (30 s: no restart): ONE ``stall``
    snapshot, taken when the step was a second old, whose batcher thread
    stands in the drill's ``sleep``, on the record of the step that slept
    (the drill sleeps inside the step's timer since ISSUE 52); the watchdog
    woke on time all along, so the sampler it holds armed never fired
    (``dump_n`` 0); the watchdog's own lateness rides the same records."""
    from tpu_voice_agent.serve.colocate import ColocatedServing
    from tpu_voice_agent.utils import chaos

    monkeypatch.setenv("CHAOS_STALL_S", "2.0")
    log = get_steplog()
    bat = _batcher(scope_engine, max_new_tokens=32)
    assert bat.generate_many(["go back"])[0].token_ids  # compiled before the drill
    log.clear()
    co = ColocatedServing(None, bat)
    restarts = get_metrics().counter_state()[0].get("engine.restarts", 0.0)
    chaos.configure("stall_step@1")
    co.start()
    co.start_watchdog(interval_s=0.1)
    try:
        res = co.submit_parse("scroll down").result(timeout=120)
    finally:
        chaos.reset()
        co.stop()
    assert res.error is None and co.stats.restarts == 0
    assert get_metrics().counter_state()[0].get("engine.restarts", 0.0) == restarts
    steps = log.steps()
    stalls = [s["stall"] for s in steps if "stall" in s]
    assert len(stalls) == 1 and "stall" in steps[0]  # the record of the step that slept
    (snap,) = stalls
    slept = steps[0]["wall_ms"]  # the drill's sleep and a tiny model's step
    assert 1000.0 <= snap["age_ms"] < slept and snap["gc_open_ms"] is None
    assert snap["batcher"] == "colocate" and snap["late_ms"] < 0.25 * slept
    (batcher,) = [t for t in snap["threads"] if t["name"] == "colocate"]
    assert batcher["frames"][0].startswith("scheduler.py:") and batcher["frames"][0].endswith(" step")
    assert any(f.endswith(" _tick") for f in batcher["frames"]) and len(batcher["frames"]) <= 6
    assert {t["name"] for t in snap["threads"]} >= {"colocate", "colocate-watchdog", "MainThread"}
    assert snap["open_spans"] == []  # the drill sleeps before the step's first stage opens
    assert snap["dump_n"] == 0 == steps[0]["stall_dump_n"] and "dump_at_ms" not in snap
    assert "stall_pending" not in log.dump()  # folded: it is the record's now
    assert steps[0]["gap_ms"] == 0.0 and all("watchdog_late_ms" in s for s in steps)
    assert "host.watchdog_late" in get_metrics().counter_state()[1]


def test_one_rid_from_submit_through_admission_to_delivery(scope_engine, tmp_path):
    """A request's three spans on the profiler's trace — ``brain.submit`` on
    the caller's thread, ``sched.admit.request`` on the batcher's,
    ``brain.deliver`` on the caller's again — carry ONE ``rid``; the wake
    latency is counted and noted; ``sched.tick`` holds every ``sched.step``."""
    from jax.profiler import ProfileData

    from tpu_voice_agent.services.brain import BatchedEngineParser, ParserError
    from tpu_voice_agent.utils.tracing import pop_stage_notes

    parser = BatchedEngineParser(scope_engine, chunk_steps=7, max_new_tokens=16)
    m = get_metrics()
    try:
        with pytest.raises(ParserError):  # random weights: truncated, typed
            parser._answer("go back", None)  # compiled before the capture
        d0 = m.counter_state()[0].get("brain.parse_deliver_ms", 0.0)
        pop_stage_notes()
        jax.profiler.start_trace(str(tmp_path))
        try:
            for text in ("scroll down", "stop"):
                with pytest.raises(ParserError):
                    parser._answer(text, None)
        finally:
            jax.profiler.stop_trace()
    finally:
        parser.runtime.stop()
    assert m.counter_state()[0]["brain.parse_deliver_ms"] > d0
    assert pop_stage_notes()["deliver_ms"] >= 0.0
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for at, line in enumerate(plane.lines):  # one line a thread (all named alike)
                for ev in line.events:
                    if ev.name.startswith(("brain.", "sched.")):
                        spans.setdefault(ev.name, []).append(
                            (at, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
    rids = {name: sorted(int(s[3]["rid"]) for s in spans[name])
            for name in ("brain.submit", "sched.admit.request", "brain.deliver")}
    assert rids["brain.submit"] == rids["sched.admit.request"] == rids["brain.deliver"]
    assert len(rids["brain.submit"]) == 2
    # the request's own spans are on ITS thread, not on the batcher's
    batcher_lines = {s[0] for s in spans["sched.step"]}
    assert not batcher_lines & {s[0] for s in spans["brain.submit"] + spans["brain.deliver"]}
    inside = lambda a, b: b[1] <= a[1] and a[2] <= b[2]
    for step in spans["sched.step"]:
        assert sum(inside(step, tick) for tick in spans["sched.tick"]) == 1
    assert len(spans["sched.admit.head"]) >= 2 and spans["sched.harvest"]


# ---------------------------------------------------- the machine's side of a step (ISSUE 52)


def _line_of(frame: str, module) -> str:
    """The source line a dump's frame (``file.py:N func``) names, in ``module``."""
    import linecache

    return linecache.getline(module.__file__, int(frame.split(":")[1].split()[0]))


@pytest.mark.parametrize("cause", ["hold", "hold_walked", "sleep"])
def test_a_long_step_names_its_cause(cause, scope_engine, monkeypatch):
    """Steps of two seconds behind the real serving loop and its watchdog, told
    apart by their records. ``hold``: a helper thread inside a native call that
    KEEPS the interpreter (``host_wait_check.drill_hold``) — every Python
    thread stands still, the watchdog oversleeps by most of the hold, and the
    stamp it holds armed, which needs no interpreter, is made WHILE the hold
    lasts — a time and no thread's frames: nobody's state is touched.
    ``hold_walked``: the same with ``machine._Sampler.frames`` set, as
    the drill on the chip sets it — every thread's frames, the helper's among
    them. ``sleep``: the chaos drill ``stall_step`` — the batcher's own thread
    sleeps, everybody else runs, the watchdog is on time, photographs the
    batcher at the line that sleeps and keeps the stamp from firing. Every
    bound is relative to the drill's own stamps or the step's own wall."""
    import host_wait_check

    from tpu_voice_agent.serve import scheduler
    from tpu_voice_agent.serve.colocate import SAMPLE_LATE_S, ColocatedServing
    from tpu_voice_agent.utils import chaos, machine

    monkeypatch.setenv("CHAOS_STALL_S", "2.0")
    log = get_steplog()
    bat = _batcher(scope_engine, max_new_tokens=32)
    assert bat.generate_many(["go back"])[0].token_ids  # compiled before the drill
    log.clear()
    drilled: dict = {}
    if cause == "sleep":
        chaos.configure("stall_step@1")
    else:
        inner = bat._step

        def held_step(timer, epoch):  # inside the step's timer, once
            if not drilled:
                # the walk is armed for the drill alone, as the tool arms it: two
                # wakes of the watchdog before the hold, while no thread runs
                # JAX's Python for it to walk into (``machine._Sampler``)
                stamp = machine.sampler()
                stamp.frames = cause == "hold_walked"
                time.sleep(0.25)
                try:
                    drilled.update(host_wait_check.drill_hold())
                finally:
                    stamp.frames = False
            return inner(timer, epoch)

        monkeypatch.setattr(bat, "_step", held_step)
    co = ColocatedServing(None, bat)
    co.start_watchdog(interval_s=0.1)  # it holds the switch armed before the first step opens
    co.start()
    try:
        res = co.submit_parse("scroll down").result(timeout=120)
    finally:
        chaos.reset()
        co.stop()
    assert res.error is None and co.stats.restarts == 0
    (rec,) = [s for s in log.steps() if "stall" in s]
    stall, wall = rec["stall"], rec["wall_ms"]
    assert rec["stall_dump_n"] == stall["dump_n"] and stall["batcher"] == "colocate"
    threads = stall.get("threads", [])  # (the photograph's or the walk's)
    assert all(len(t["frames"]) <= 6 for t in threads)
    batcher = next((t for t in threads if t["name"] == "colocate"), None)
    if cause == "sleep":
        # the batcher in ``time.sleep``, photographed by a watchdog that was on
        # time — and so kept the stamp from firing
        assert stall["dump_n"] == 0 and "dump_at_ms" not in stall
        assert batcher["frames"][0].endswith(" step")
        assert "time.sleep(" in _line_of(batcher["frames"][0], scheduler)
        assert rec["watchdog_late_ms"] < 0.5 * wall and stall["late_ms"] < 0.5 * wall
        assert 1000.0 <= stall["age_ms"] <= wall
        return
    held = (drilled["end_ns"] - drilled["begin_ns"]) / 1e6
    begin_at, end_at = ((drilled[k] - rec["t0_ns"]) / 1e6 for k in ("begin_ns", "end_ns"))
    assert not drilled["alive"] and held >= 2000.0
    # made once, when the watchdog had not woken for what it armed the switch
    # with at its last wake (within the interval before the hold; a file's
    # mtime is a tick coarse) — WHILE the interpreter was held
    assert stall["dump_n"] == 1 and stall["after_ms"] == 1e3 * (0.1 + SAMPLE_LATE_S)
    assert begin_at + stall["after_ms"] - 100.0 - 20.0 <= stall["dump_at_ms"] <= end_at - 500.0
    # every Python thread stood still: the watchdog overslept most of the hold ...
    assert rec["watchdog_late_ms"] >= 0.5 * held
    # ... and the batcher's thread was not waiting for a CPU
    assert rec.get("run_delay_ms", 0.0) <= 0.25 * held
    if cause == "hold":
        # a time alone; the frames are the watchdog's photograph's, if it still
        # found the step open: taken late, under the interpreter's lock, when
        # the hold was over
        assert set(stall) - {"dump_n", "after_ms", "dump_at_ms", "batcher"} <= {
            "threads", "age_ms", "late_ms", "gc_open_ms", "open_spans"}
        if "age_ms" in stall:
            assert stall["late_ms"] >= 0.5 * held and stall["age_ms"] >= end_at - 1.0
    else:
        # the thread that held it, by its frame (it is gone when the step closes:
        # the dump's ident finds no name any more)
        (helper,) = [t for t in threads if t["frames"] and t["frames"][0].endswith(" hold")]
        assert helper["name"] == str(drilled["ident"])
        assert "usleep(" in _line_of(helper["frames"][0], host_wait_check)
        assert any(f.endswith(" drill_hold") for f in batcher["frames"])  # it waits for the helper


def test_a_stopped_process_is_sampled_when_it_runs_again(tmp_path):
    """``SIGSTOP``, then ``SIGCONT`` two seconds later, from a child
    (``host_wait_check.drill_stop``): the whole process is not run, the stamp
    that needs no interpreter with it — it is made when the process runs
    again, NOT when its time ran out, though the watchdog wakes at that same
    moment and reaches for the timer. In a process of its own: a stopped test
    process reads as a stopped job to a shell."""
    script = tmp_path / "stopped.py"
    script.write_text(
        "import json, sys, time\n"
        f"sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'tools')!r}]\n"
        "import host_wait_check\n"
        "from tpu_voice_agent.utils.steplog import StepLog\n"
        "from tpu_voice_agent.serve.colocate import ColocatedServing\n"
        "co = ColocatedServing(None, None)\n"  # its watchdog alone: it holds the switch
        "co.start_watchdog(interval_s=0.25)\n"
        "time.sleep(0.6)\n"
        "t = StepLog(max_steps=8, enabled=True, sampler=True).timer()\n"
        "t.stage('sched.admit')\n"
        "drilled = host_wait_check.drill_stop()\n"
        "print(json.dumps({'drill': drilled, 'rec': t.finish()}))\n")
    proc = subprocess.run([sys.executable, str(script)], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    drilled, rec = out["drill"], out["rec"]
    if "refused" in drilled:
        pytest.skip(f"no process may stop this one here: {drilled['refused']}")
    stall = rec["stall"]
    cont_at = (drilled["end_ns"] - rec["t0_ns"]) / 1e6
    assert drilled["end_ns"] - drilled["begin_ns"] >= 2e9 and rec["wall_ms"] >= cont_at
    assert stall["dump_n"] == 1 == rec["stall_dump_n"] and "threads" not in stall
    # not before the SIGCONT (a file's mtime is a tick coarse): the stamp stood still too
    assert cont_at - 20.0 <= stall["dump_at_ms"] <= rec["wall_ms"]
    assert stall["after_ms"] == 750.0 and rec["watchdog_late_ms"] >= 1000.0


@pytest.mark.parametrize("lacking,keys", [
    ("schedstat", ["run_delay_ms"]), ("cpu.stat", ["throttled_ms"]),
    ("a kernel that counts nothing", ["majflt"]),
    ("every source", ["run_delay_ms", "majflt", "throttled_ms"])])
def test_a_source_the_machine_lacks_leaves_its_key_out(lacking, keys, monkeypatch):
    """A machine without a source (its probe fails — or, as the benchmark's
    machines do, its kernel answers ``getrusage`` and counts NOTHING, not even
    the switch a sleep is): the key is in NO record — never ``None``, never a
    made-up 0.0 that would say "no delay" — the other counters stay, and the
    reader ``step_fields`` finds nothing to read."""
    import types

    from benchmark.readers import step_fields
    from tpu_voice_agent.utils import machine

    there = {k for k, has in machine.counters().sources().items() if has}
    if "majflt" in keys:
        zeros = types.SimpleNamespace(ru_minflt=0, ru_majflt=0, ru_nvcsw=0, ru_nivcsw=0)
        monkeypatch.setattr(machine, "resource", types.SimpleNamespace(
            RUSAGE_SELF=0, RUSAGE_THREAD=1, getrusage=lambda who: zeros))
    if len(keys) > 1 or "majflt" not in keys:
        real = machine._open
        gone = ("schedstat", "cpu.stat") if len(keys) > 1 else (lacking,)
        monkeypatch.setattr(machine, "_open", lambda path: -1 if any(g in path for g in gone) else real(path))
    monkeypatch.setattr(machine, "_MACHINE", None)  # probed anew, behind the patch
    log = StepLog(max_steps=8, enabled=True)
    recs = [log.timer().finish() for _ in range(3)]
    assert not any(machine.counters().sources()[k] for k in keys)
    for rec in recs:
        assert not set(keys) & set(rec) and not any(v is None for v in rec.values())
        assert there - set(keys) <= set(rec)  # the sources it has stay
    for key in keys:
        assert step_fields.read({"steps": recs}, what=key, stat="sum", per="steps") is None
    for key in there - set(keys):
        assert step_fields.read({"steps": recs}, what=key, stat="sum") >= 0


def test_a_ledger_that_is_off_arms_nothing_and_opens_no_file(monkeypatch):
    """``StepLog(enabled=False)`` reads no ``/proc`` and opens no file, and a
    watchdog over a ledger that is off arms no timer and takes no signal; a
    ledger that is on reads the counters and still opens no file (the process
    has ONE sampler, and only a watchdog arms it)."""
    from tpu_voice_agent.serve.colocate import ColocatedServing
    from tpu_voice_agent.utils import machine

    opened = []
    monkeypatch.setattr(machine, "_MACHINE", None)
    monkeypatch.setattr(machine, "_SAMPLER", None)
    monkeypatch.setattr(machine, "_open", lambda path: opened.append(path) or -1)
    monkeypatch.setattr(machine.tempfile, "TemporaryFile", lambda *a, **kw: pytest.fail("opened a file"))
    monkeypatch.setattr(machine.tempfile, "TemporaryDirectory", lambda *a, **kw: pytest.fail("made a directory"))
    monkeypatch.setattr(machine.faulthandler, "dump_traceback_later", lambda *a, **kw: pytest.fail("armed the walk"))
    monkeypatch.setattr(machine.ctypes, "CDLL", lambda *a, **kw: pytest.fail("reached for a timer"))
    rec = StepLog(max_steps=8, enabled=False, sampler=True).timer().finish()
    assert machine._MACHINE is None and machine._SAMPLER is None and not opened
    assert not set(machine.MACHINE_KEYS) & set(rec) and "stall_dump_n" not in rec
    monkeypatch.setattr(get_steplog(), "enabled", False)
    co = ColocatedServing(None, None)
    co.start_watchdog(interval_s=0.02)
    time.sleep(0.1)
    co.stop()
    assert machine._SAMPLER is None and not opened
    rec = StepLog(max_steps=8, enabled=True, sampler=True).timer().finish()
    assert machine._SAMPLER is None and "stall_dump_n" not in rec
    assert opened and machine._MACHINE is not None  # on: it looked for the counters


def test_the_sampler_never_fires_in_ordinary_steps(scope_engine):
    """Armed anew at every wake of the watchdog and never fired: over twenty
    ordinary steps behind the real serving loop nothing is written, no record
    holds a ``stall`` or a ``stall_dump_n``, and a watchdog that stops leaves
    nothing armed — nor does one that recovers a dead loop hold it meanwhile."""
    import os

    from tpu_voice_agent.serve.colocate import ColocatedServing
    from tpu_voice_agent.utils import machine

    log = get_steplog()
    bat = _batcher(scope_engine, max_new_tokens=32)
    assert bat.generate_many(["go back"])[0].token_ids  # compiled before the count
    log.clear()
    co = ColocatedServing(None, bat)
    co.start_watchdog(interval_s=0.05)
    co.start()
    try:
        while len(log.steps()) < 20:
            futs = [co.submit_parse(t) for t in ("scroll down", "play some jazz", "what time is it")]
            assert all(f.result(timeout=120).error is None for f in futs)
        assert machine.armed_sampler() is not None
    finally:
        co.stop()
    steps = log.steps()
    short = [s for s in steps if s["wall_ms"] < 900.0 * log.stall_after_s()]
    assert len(short) >= 20 or len(short) == len(steps)
    assert not any("stall_dump_n" in s or "stall" in s for s in short)
    stamp = machine.sampler()
    assert machine.armed_sampler() is None and not stamp._left()  # the stopped watchdog let go of it
    if max(s["watchdog_late_ms"] for s in steps) < 400.0:  # (it woke in time throughout)
        assert os.listdir(stamp._dir.name) == []
        assert stamp.file is None or os.fstat(stamp.file.fileno()).st_size == 0  # (a walk's, of a test before)


def test_the_os_counters_bracket_a_step_and_a_new_thread_probes_anew():
    """The counters are read at a step's two ends: counts are whole numbers,
    times are milliseconds, nothing is negative, and a key the machine has no
    source for is not there to ask for. A thread's own delay comes from the
    file THAT thread opened: a loop the watchdog restarts is a new thread with
    a file of its own, which closes with it."""
    import os
    import threading

    from tpu_voice_agent.utils import machine

    def a_step() -> dict:
        t = StepLog(max_steps=8, enabled=True).timer()
        t.stage("sched.admit")
        time.sleep(0.05)
        return t.finish()

    rec = a_step()
    has = {k for k, there in machine.counters().sources().items() if there}
    assert set(machine.MACHINE_KEYS) & set(rec) == has
    assert all(rec[k] >= 0 for k in has)
    if "majflt" in has:
        assert isinstance(rec["majflt"], int)
    if "run_delay_ms" in has:  # a thread that slept was not kept waiting for its whole step
        assert rec["run_delay_ms"] < rec["wall_ms"]
    assert "stall_dump_n" not in rec  # an ad-hoc ledger arms no sampler
    seen: dict = {}

    def restarted_loop() -> None:
        seen["rec"] = a_step()
        seen["fd"] = machine.counters()._own.file.fd

    th = threading.Thread(target=restarted_loop)
    th.start()
    th.join(timeout=60)
    assert not th.is_alive() and set(seen["rec"]) ^ set(rec) <= {"gc"}  # (a collection falls where it falls)
    mine = machine.counters()._own.file.fd
    if "run_delay_ms" in has:
        assert seen["fd"] >= 0 and seen["fd"] != mine
        os.fstat(mine)  # this thread's is open; the other's closed with its thread
        try:  # (its number may be somebody else's file by now)
            target = os.readlink(f"/proc/self/fd/{seen['fd']}")
        except OSError:
            target = ""
        assert not target.endswith("/schedstat")
