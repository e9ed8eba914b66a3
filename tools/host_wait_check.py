#!/usr/bin/env python3
"""What holds the batcher's thread, read from an UNTRACED run: the step
ledger's ISSUE 36 keys over one cell's traffic, with the profiler off.

The benchmark's per-layer metrics come from ``--trace 1`` runs, and the
profiler costs 7.5 % of a flood's rate and runs on the same interpreter
(PERF.md section 6, PR 24): a traced run's off-CPU share is a reading of the
PROFILED program. This serves one cell of ``BENCHMARK.json`` as
``benchmark/run.py`` does — its configuration through ``benchmark/builders``,
its traffic file through the load generator's process — for ``--seconds``,
and prints the medians of the ring's new keys over the steps of the window:
the head of a step (``head_ms``, its off-CPU share, others' CPU in it; what
its admissions spent tokenizing and how many ids the head's memo gave them), the
gap before a step, the lock wait and the collections a step, every stage's
wall / CPU / others' CPU (``others_cpu_ms["readback"]`` with one client is
the runtime's floor), the wake latency of an answer, collections a second,
the watchdog's worst lateness, and the longest step's whole record (every
record goes to ``chiprun_out/host_wait_records_<cell>.jsonl``). Wall times
are medians; what the two CPU clocks read are MEANS (they tick every 10 ms on
the benchmark's machines). With ``--profile-s N`` the profiler runs over N
seconds in the middle of the same window and the steps that closed inside
that stretch are summarised apart (the steps under the profiler's own start
and stop are left out of both): traced and untraced readings of one process,
side by side. Last, what the
instrumentation costs where it runs: nanoseconds a ``gc.callbacks`` pair and
a reading of the three clocks, and (ISSUE 52) of each of the OS's counters a
record carries and of arming the stamp that needs no interpreter (the watchdog
does that once a wake) — with which of those sources this machine has.

``--drill hold|stop`` (ISSUE 52) stalls the live stack for ``DRILL_S`` in the
middle of the window, in one of the two ways a long step comes about, and
prints the drill's own stamps beside the record of the step it fell into:
``hold`` — a helper thread inside a native call that KEEPS the interpreter
(``ctypes.PyDLL(None).usleep``): every Python thread stands still, the Python
watchdog oversleeps, the switch it holds armed fires while the hold lasts
(``stall.dump_at_ms`` about ``after_ms`` past the watchdog's last wake); for
the drill's few seconds the sampler walks every thread's frames
(``machine._Sampler.frames``), so the helper's frame is among
``stall.threads``; ``stop`` — a child process sends this one ``SIGSTOP`` and,
later, ``SIGCONT``: the stamp stands still with the rest (``dump_at_ms`` not
before the ``SIGCONT``) and holds the frames of the one thread it reached.

    python3 tools/host_wait_check.py --workload parse_flood [--seed 7] [--seconds 20] [--profile-s 3] [--drill hold]

One cell a process (each fills most of the chip). A line of JSON a run, on
stdout and appended to ``chiprun_out/host_wait_check.jsonl``. With
JAX_PLATFORMS=cpu at the configuration's rehearsal widths (no timing is a
device's there)."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRILL_S = 2.0  # past the sampler's least timeout (a second)
# the child of ``drill_stop``: stops its parent, lets it run again, says when
_STOPPER = """
import os, signal, sys, time
pid, seconds = int(sys.argv[1]), float(sys.argv[2])
os.kill(pid, signal.SIGSTOP)
try:
    stopped = time.time_ns()
    time.sleep(seconds)
finally:
    cont = time.time_ns()
    os.kill(pid, signal.SIGCONT)
print(stopped, cont)
"""


def med(values) -> float | None:
    values = list(values)
    return round(statistics.median(values), 4) if values else None


def mean(values) -> float | None:
    """For what is read on the CPU clocks: where they tick every 10 ms (the
    benchmark's machines) one reading is 0, 10 or 20 and only a mean resolves."""
    values = list(values)
    return round(statistics.fmean(values), 4) if values else None


def summary(steps: list[dict], seconds: float) -> dict:
    """The new keys over ``steps`` (records of steps that ran a chunk)."""
    from tpu_voice_agent.utils.machine import MACHINE_KEYS
    from tpu_voice_agent.utils.steplog import STAGES

    if not steps:
        return {"steps": 0}
    adm = [s for s in steps if s.get("admitted") and "head_ms" in s]
    head = sum(s["head_ms"] for s in adm)
    admissions = [a for s in adm for a in s.get("admissions", [])]
    out = {
        "steps": len(steps), "wall_ms": med(s["wall_ms"] for s in steps),
        "head_ms": med(s["head_ms"] for s in adm),
        "head_off_cpu_share": round(100 * sum(s["head_ms"] - s["head_cpu_ms"] for s in adm) / head, 2)
        if head else None,
        "head_ms_mean": mean(s["head_ms"] for s in adm),
        "head_cpu_ms_mean": mean(s["head_cpu_ms"] for s in adm),
        "head_others_cpu_ms_mean": mean(s["head_others_cpu_ms"] for s in adm),
        # what the head holds of tokenizing (ISSUE 53): an admission's walk, a step's
        # admissions' walks together, and the ids an admission took from the engine's
        # memo of the prompt head (a program that keeps none writes no such key)
        "tokenize_ms": med(a["tokenize_ms"] for a in admissions if "tokenize_ms" in a),
        "tokenize_ms_per_step_mean": mean(sum(a.get("tokenize_ms", 0.0) for a in s.get("admissions", []))
                                          for s in adm),
        "head_ids_reused": med(a["head_ids_reused"] for a in admissions if "head_ids_reused" in a),
        "gap_ms": med(s["gap_ms"] for s in adm), "gap_cpu_ms_mean": mean(s["gap_cpu_ms"] for s in adm),
        "gap_others_cpu_ms_mean": mean(s["gap_others_cpu_ms"] for s in adm),
        "lock_wait_ms_per_step": round(sum(s["lock_wait_ms"] for s in steps) / len(steps), 4),
        "lock_wait_ms_max": max(s["lock_wait_ms"] for s in steps),
        "gc_ms_per_step": round(sum(s["gc_ms"] for s in steps) / len(steps), 4),
        "gc_longest_ms": max(s["gc_max_ms"] for s in steps),
        "gc_per_s": round(sum(s["gc_n"] for s in steps) / seconds, 2),
        "gc_by_gen": {g: sum(1 for s in steps for e in s.get("gc", []) if e["gen"] == g) for g in (1, 2)},
        "watchdog_late_ms_max": max(s["watchdog_late_ms"] for s in steps),
        "stalls": sum(1 for s in steps if "stall" in s),
    }
    # the machine's side (ISSUE 52): a step's mean of each counter the machine
    # has — a key it lacks is in no record, and not in this summary either
    for k in (*MACHINE_KEYS, "stall_dump_n"):
        have = [s[k] for s in steps if k in s]
        if have:
            out[k + "_per_step"], out[k + "_max"] = round(sum(have) / len(have), 4), max(have)
    # every stage on the three clocks, MEANS: wall, this thread's CPU, the others' CPU
    out["stages_mean"] = {k: [mean(s["stages"][k] for s in steps if k in s["stages"]),
                              mean(s["cpu_ms"][k] for s in steps if k in s["cpu_ms"]),
                              mean(s["others_cpu_ms"][k] for s in steps if k in s["others_cpu_ms"])]
                          for k in STAGES if any(k in s["stages"] for s in steps)}
    # what ISSUE 36 asks of every record of an untraced run
    keys = {"cpu_ms", "others_cpu_ms", "gap_ms", "lock_wait_ms", "gc_ms", "gc_max_ms", "gc_n",
            "watchdog_late_ms", "head_ms"}
    out["records_lacking_a_key"] = sum(1 for s in steps if not keys <= set(s))
    out["cpu_over_wall_ms_max"] = round(max(sum(s["cpu_ms"].values()) - s["wall_ms"] for s in steps), 3)
    out["head_over_admit_prefill_ms_max"] = round(max(
        s["head_ms"] - s["stages"].get("admit", 0.0) - s["stages"].get("prefill", 0.0)
        for s in steps if "head_ms" in s), 3)
    return out


def drill_hold(seconds: float = DRILL_S) -> dict:
    """A helper thread inside a native call that KEEPS the interpreter for
    ``seconds`` (``PyDLL`` releases no lock around the call): the process runs,
    and no Python thread of it does. Returns the hold's own stamps
    (``time.time_ns``) and the helper's ident, by which a dump names it once
    the thread is gone."""
    import ctypes

    usleep = ctypes.PyDLL(None).usleep
    usleep.argtypes, usleep.restype = [ctypes.c_uint], ctypes.c_int
    out: dict = {"drill": "hold"}

    def hold() -> None:
        out.update(ident=threading.get_ident(), begin_ns=time.time_ns())
        usleep(int(seconds * 1e6))
        out["end_ns"] = time.time_ns()

    th = threading.Thread(target=hold, name="drill-hold")
    th.start()
    th.join(timeout=seconds + 60)
    out["alive"] = th.is_alive()
    return out


def drill_stop(seconds: float = DRILL_S) -> dict:
    """A child process sends THIS process ``SIGSTOP`` and, ``seconds`` later,
    ``SIGCONT``: the whole process is not run, its sampler with it. Returns the
    child's stamps of both signals (``time.time_ns``), or ``refused`` where the
    machine lets no process stop this one."""
    import subprocess

    child = subprocess.run([sys.executable, "-c", _STOPPER, str(os.getpid()), str(seconds)],
                           capture_output=True, text=True, timeout=seconds + 60)
    if child.returncode != 0:
        return {"drill": "stop", "refused": child.stderr.strip()[-300:]}
    stopped, cont = (int(v) for v in child.stdout.split())
    return {"drill": "stop", "begin_ns": stopped, "end_ns": cont}


def instrument_cost(n: int = 20000) -> dict:
    """Nanoseconds a ``gc.callbacks`` pair (stamp + annotation + event) and a
    reading of the three clocks, in this process, with the profiler off; and
    (ISSUE 52) of what a step's two ends read of the machine, source by source
    as ``utils/machine.py`` reads them, of arming the stamp that needs no
    interpreter (the watchdog's, once a wake) and arming + cancelling it, and
    of the whole of a step's open and close."""
    from tpu_voice_agent.utils import machine, steplog

    def ns_each(fn, reps: int) -> float:
        t0 = time.perf_counter_ns()
        for _ in range(reps):
            fn()
        return round((time.perf_counter_ns() - t0) / reps, 1)

    counters, stamp = machine.counters(), machine.sampler()
    cost = {"machine_read_ns": ns_each(counters.read, 2000)}
    for source in (counters.run_delay_ms, counters.majflt, counters.throttled_ms):
        if source() is not None:  # a source the machine lacks has no cost, and no key
            cost[source.__name__ + "_read_ns"] = ns_each(source, 2000)
    if stamp is not None:
        held = stamp.owner  # the serving watchdog: it arms the switch anew within its interval
        cost["sampler_arm_ns"] = ns_each(lambda: stamp.arm(cost, 60.0), 2000)
        cost["sampler_arm_cancel_ns"] = ns_each(lambda: (stamp.arm(cost, 60.0), stamp.cancel(cost)), 2000)
        if held is not None:
            stamp.arm(held, 60.0)
    log = steplog.StepLog(max_steps=8, enabled=True, sampler=True)
    cost["step_open_close_ns"] = ns_each(lambda: log.timer().finish(), 1000)
    bare = steplog.StepLog(max_steps=8, enabled=False)
    cost["step_open_close_ledger_off_ns"] = ns_each(lambda: bare.timer().finish(), 1000)

    info = {"generation": 0, "collected": 0, "uncollectable": 0}
    steplog._install_gc()
    t0 = time.perf_counter_ns()
    for _ in range(n):
        steplog._on_gc("start", info)
        steplog._on_gc("stop", info)
    pair = (time.perf_counter_ns() - t0) / n
    steplog._EVENTS.clear()
    t0 = time.perf_counter_ns()
    for _ in range(n):
        steplog._clocks()
    return {"gc_callback_pair_ns": round(pair, 1),
            "three_clocks_ns": round((time.perf_counter_ns() - t0) / n, 1), **cost}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--profile-s", type=float, default=0.0,
                    help="profile this many seconds in the middle of the window")
    ap.add_argument("--drill", choices=("hold", "stop"),
                    help=f"stall the stack for {DRILL_S} s in the middle of the window")
    args = ap.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    from benchmark.lib.manifest import load_cell, load_code, load_manifest
    from benchmark.run import Client, Tracer, program_env, say

    class StampedTracer(Tracer):
        """The benchmark's tracer, and when its ``stop_trace`` had returned:
        starting and stopping the profiler burns a second of CPU beside the
        server's threads, and the steps under that are neither reading."""

        done_wall_s = None

        def run(self) -> None:
            super().run()
            self.done_wall_s = time.time()

    cell = load_cell(load_manifest(), args.workload)
    config, traffic = cell["config"], cell["traffic"]
    program_env(config)
    import jax

    from tpu_voice_agent.utils import get_metrics
    from tpu_voice_agent.utils.compilecache import place_compile_cache
    from tpu_voice_agent.utils.steplog import get_steplog

    place_compile_cache()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    client = Client()
    served = load_code("builders", config["builder"]).build(config, rehearsal, say)
    try:
        gen = {"generator": traffic["generator"], "traffic": traffic, "urls": served.urls,
               "seed": args.seed, "seconds": args.seconds}
        client.command(dict(gen, cmd="warm"))
        tracer = None
        if args.profile_s:
            tracer = StampedTracer(os.path.join(ROOT, ".bench_trace"),
                            max(0.0, (args.seconds - args.profile_s) / 2), args.profile_s)
            tracer.start()
        edges: dict = {}
        drilled: dict = {}

        def drill() -> None:
            from tpu_voice_agent.utils import machine

            time.sleep(args.seconds / 2 - 1.5)
            # ``hold`` names the thread that holds the interpreter: every
            # thread's frames, for these few seconds alone (the watchdog arms
            # the switch anew at each wake, half a second apart)
            stamp = machine.armed_sampler()
            if stamp is not None:
                stamp.frames = args.drill == "hold"
            time.sleep(1.5)
            try:
                drilled.update((drill_hold if args.drill == "hold" else drill_stop)())
            finally:
                if stamp is not None:
                    stamp.frames = False

        def on_event(msg: dict) -> None:
            edges[msg["ev"]] = (msg["t"], get_metrics().counter_state()[0])
            if msg["ev"] == "window_start" and tracer is not None:
                tracer.go.set()
            if msg["ev"] == "window_start" and args.drill:
                threading.Thread(target=drill, name="drill", daemon=True).start()

        client.command(dict(gen, cmd="run"), on_event)
        if tracer is not None:
            tracer.join(timeout=120)
        (t0, c0), (t1, c1) = edges["window_start"], edges["window_end"]
        steps = [s for s in get_steplog().steps() if t0 <= s["t_s"] <= t1 and s.get("forwards")]
        inside, outside = [], steps
        if tracer is not None and tracer.anchor_wall_s and tracer.done_wall_s:
            lo = tracer.anchor_wall_s
            inside = [s for s in steps if lo <= s["t0_ns"] / 1e9 and s["t_s"] <= lo + args.profile_s]
            # neither: the steps under the profiler's own start and stop
            outside = [s for s in steps if s["t_s"] < t0 + tracer.at_s - 0.2
                       or s["t0_ns"] / 1e9 > tracer.done_wall_s + 0.2]
        done = c1.get("brain.parse_completed", 0.0) - c0.get("brain.parse_completed", 0.0)
        dev = jax.devices()[0]
        out = {
            "workload": args.workload, "seed": args.seed, "seconds": round(t1 - t0, 3),
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "steplog": get_steplog().enabled, "requests": done,
            "tokens_per_s": round((c1.get("scheduler.tokens_generated", 0.0)
                                   - c0.get("scheduler.tokens_generated", 0.0)) / (t1 - t0), 2),
            "deliver_ms_mean": round((c1.get("brain.parse_deliver_ms", 0.0)
                                      - c0.get("brain.parse_deliver_ms", 0.0)) / done, 4) if done else None,
            "untraced": summary(outside, sum(s["wall_ms"] + s["gap_ms"] for s in outside) / 1e3),
        }
        if inside:
            out["profiled"] = summary(inside, sum(s["wall_ms"] + s["gap_ms"] for s in inside) / 1e3)
        if outside:
            out["longest_step"] = max(outside, key=lambda s: s["wall_ms"])
        from tpu_voice_agent.utils import machine

        out["machine_sources"] = machine.counters().sources()  # as THIS thread finds them
        if args.drill:
            # the drill's stamps on the clock of the step it fell into (ms from
            # that step's start, as ``stall.dump_at_ms`` is), beside its record
            hit = next((s for s in steps if "begin_ns" in drilled
                        and s["t0_ns"] <= drilled["begin_ns"] <= s["t1_ns"]), None)
            if hit is not None:
                drilled.update(begin_at_ms=round((drilled["begin_ns"] - hit["t0_ns"]) / 1e6, 3),
                               end_at_ms=round((drilled["end_ns"] - hit["t0_ns"]) / 1e6, 3))
            out["drill"], out["drilled_step"] = drilled, hit
        with open(os.path.join(ROOT, "chiprun_out", f"host_wait_records_{args.workload}.jsonl"), "w") as f:
            f.writelines(json.dumps(s) + "\n" for s in steps)  # every record of the window
        out["cost"] = instrument_cost()
    finally:
        client.close()
        served.close()
    line = json.dumps(out)
    with open(os.path.join(ROOT, "chiprun_out", "host_wait_check.jsonl"), "a") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
