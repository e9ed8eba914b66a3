#!/usr/bin/env python3
"""The benchmark's command: ONE run of ONE cell, as a new process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It builds the cell's configuration through the program's own entry points
(``benchmark/builders/``), warms every shape the cell's traffic uses (all of
that is ``setup_s``), lets a child process that never touches JAX offer the
cell's traffic for ``--seconds`` (``benchmark/generators/``), checks the
outputs and compares the served engines with the plain references
(``benchmark/reference/``), and prints one JSON object as its last line.
Without a TPU in the peaks table it exits non-zero and prints no result —
unless ``JAX_PLATFORMS=cpu`` asks for the rehearsal, which runs the same
steps at test widths and reports ``"platform": "cpu"`` with ``correct``
false: a CPU number is never a device number.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # setup_s runs from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(f"[benchmark] {msg}", flush=True)


def refuse(msg: str) -> int:
    """No result line: stderr only."""
    print(f"[benchmark] REFUSED: {msg}", file=sys.stderr, flush=True)
    return 2


class Client:
    """The load generator's process (``client.py``), started early so that
    its start-up overlaps the model build."""

    def __init__(self):
        env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py")], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, cwd=ROOT, env=env)

    def command(self, cmd: dict, on_event=None) -> dict:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        for line in self.proc.stdout:
            msg = json.loads(line)
            if msg["ev"] == "done":
                if "error" in msg:
                    raise RuntimeError(f"load generator failed: {msg['error']}")
                return msg["result"]
            if on_event is not None:
                on_event(msg)
        raise RuntimeError("load generator exited without a result")

    def close(self) -> None:
        try:
            self.proc.stdin.close()
            self.proc.wait(timeout=20)
        except Exception:
            self.proc.kill()
            self.proc.wait()


class Tracer(threading.Thread):
    """``jax.profiler`` over a short steady stretch of the window, in the
    process that holds the chip, with the two anchor annotations that put the
    step ledger's wall-clock stages on the trace's clock."""

    def __init__(self, out_dir: str, at_s: float, for_s: float):
        super().__init__(daemon=True)
        self.out_dir, self.at_s, self.for_s = out_dir, at_s, for_s
        self.go = threading.Event()
        self.anchor_wall_s = None
        self.error = None

    def run(self) -> None:
        import jax

        from benchmark.lib.trace import ANCHOR, ANCHOR_END

        self.go.wait()
        time.sleep(self.at_s)
        try:
            jax.profiler.start_trace(self.out_dir)
            with jax.profiler.TraceAnnotation(ANCHOR):
                self.anchor_wall_s = time.time()
            time.sleep(self.for_s)
            with jax.profiler.TraceAnnotation(ANCHOR_END):
                pass
            jax.profiler.stop_trace()
        except Exception as e:  # a failed trace fails the run, visibly
            self.error = f"{type(e).__name__}: {e}"


class CounterWatch(threading.Thread):
    """The instants, on the benchmark's own clock, at which one of the
    program's counters moved. The batcher reports tokens once per chunk of 16
    forwards (1.4 s under load), so a 45 s window holds 33 reports: tokens of
    the window over the window is a staircase that reads the same for any
    step time between 45/34 and 45/33 s and then drops by 3 %. A rate over
    whole reports has no such edge."""

    def __init__(self, metrics, name: str, every_s: float = 0.005):
        super().__init__(daemon=True)
        self.metrics, self.name, self.every_s = metrics, name, every_s
        self.stop = threading.Event()
        self.marks: list[tuple[float, float]] = []  # (perf_counter, value) at each change

    def run(self) -> None:
        last = self.metrics.counter_state()[0].get(self.name, 0.0)
        while not self.stop.wait(self.every_s):
            now = self.metrics.counter_state()[0].get(self.name, 0.0)
            if now != last:
                self.marks.append((time.perf_counter(), now))
                last = now

    def rate(self) -> float | None:
        """Counted between the first and the last report inside the window,
        over the time between those two reports."""
        if len(self.marks) < 2:
            return None
        (t_a, v_a), (t_b, v_b) = self.marks[0], self.marks[-1]
        return (v_b - v_a) / (t_b - t_a)


def open_device(chips: int, rehearsal: bool):
    """(device, peaks) as JAX reports them, or a refusal string."""
    import jax

    from benchmark.lib import peaks as pk

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if rehearsal:
        say("REHEARSAL platform=cpu: test widths, no device number is printed")
        return device, None, None
    if device["platform"] != "tpu" or device["kind"] not in pk.PEAK_TABLE:
        return device, None, (f"needs a TPU in benchmark/lib/peaks.py PEAK_TABLE "
                              f"({sorted(pk.PEAK_TABLE)}); JAX reports {device}")
    if device["count"] < chips:
        return device, None, f"cell needs {chips} chip(s), JAX reports {device['count']}"
    return device, pk.peaks_for(device["kind"]), None


def run_window(client: Client, gen: dict, tracer: Tracer | None, watch_tokens: bool):
    """The generator's run; the program's counters are read (never reset) at
    the two edges the generator announces, and where the cell reports a
    token rate, at every report between them."""
    from tpu_voice_agent.utils import get_metrics

    metrics = get_metrics()
    edges: dict = {}
    watch = CounterWatch(metrics, "scheduler.tokens_generated") if watch_tokens else None

    def on_event(msg: dict) -> None:
        counters, _ = metrics.counter_state()
        edges[msg["ev"]] = {"t": msg["t"], "counters": counters}
        if msg["ev"] == "window_start":
            if tracer is not None:
                tracer.go.set()
            if watch is not None:
                watch.start()
        elif watch is not None:
            watch.stop.set()
            watch.join()

    out = client.command(dict(gen, cmd="run"), on_event)
    if tracer is not None:
        tracer.join(timeout=120)
    return out, edges, watch


def judge_parses(records: list[dict]) -> tuple[int, list[str]]:
    """Every answered /parse is a schema-valid plan; the engine's typed
    truncation is a healthy engine's ending but no plan, so it counts as
    failed without making the run incorrect."""
    from tpu_voice_agent.schemas import ParseResponse

    problems = []
    for r in records:
        if r["outcome"] == "plan":
            try:
                ParseResponse.model_validate(r["body"])
            except ValueError as e:
                r["outcome"] = "failed"
                problems.append(f"/parse 200 with a body that is no plan: {e}"[:200])
        elif r["outcome"] == "failed":
            problems.append(f"/parse ended in {r['status']} {r.get('error') or r['body']}"[:200])
    say(f"requests in window {len(records)}: " + ", ".join(
        f"{k} {sum(r['outcome'] == k for r in records)}" for k in ("plan", "truncated", "failed")))
    return sum(r["outcome"] != "plan" for r in records), problems


def judge_utterances(utterances: list[dict]) -> tuple[int, list[str]]:
    """Every utterance has one ``transcript_final`` and an ``intent`` from the
    engine (never the rule parser's degraded answer)."""
    problems = []
    for u in utterances:
        types = [e["type"] for e in u["events"]]
        if types.count("transcript_final") != 1:
            problems.append(f"utterance without exactly one transcript_final: {types}"[:200])
        if any(e.get("degraded") for e in u["events"]):
            why = [e.get("message") for e in u["events"] if e["type"] == "warn"]
            problems.append(f"degraded / rule-parser answer: {why}"[:200])
        if u["ended"] == "timeout":
            problems.append("utterance timed out")
        elif u["ended"] == "error":
            err = next(e for e in u["events"] if e["type"] == "error")
            if "decode truncated after" not in str(err.get("detail")):
                problems.append(f"utterance ended in {err}"[:200])
    finals = [next((e.get("text", "")[:24] for e in u["events"] if e["type"] == "transcript_final"),
                   None) for u in utterances[:6]]
    say(f"utterances in window {len(utterances)}: " + ", ".join(
        f"{k} {sum(u['ended'] == k for u in utterances)}" for k in ("intent", "error", "timeout"))
        + f"; transcripts {json.dumps(finals)}")
    return sum(u["ended"] != "intent" for u in utterances), problems


def end_to_end(records, utterances, traffic, seconds, setup_s, tokens, window_s, watch) -> dict:
    """The end-to-end metrics, each over ALL the window's requests."""
    from benchmark.lib.stats import percentile

    e2e = {"setup_s": setup_s}
    if watch is not None and watch.rate() is not None:
        (t_a, v_a), (t_b, v_b) = watch.marks[0], watch.marks[-1]
        e2e["out_tokens_per_s"] = watch.rate()
        say(f"token rate: {len(watch.marks)} reports in the window, {v_b - v_a:.0f} tokens in the "
            f"{t_b - t_a:.3f}s between the first and the last = {watch.rate():.3f}/s (all "
            f"{tokens:.0f} tokens of the window over {window_s:.3f}s: {tokens / window_s:.3f}/s)")
    fail_ms = traffic["timeout_s"] * 1e3
    if utterances:
        lat = [next((e["ms_from_speech_end"] for e in u["events"] if e["type"] == "intent"), fail_ms)
               for u in utterances]
        e2e["voice_to_intent_mean_ms"] = sum(lat) / len(lat)
        firsts = lambda u, t: next((round(e["ms_from_speech_end"]) for e in u["events"]
                                    if e["type"] == t), None)
        say("utterances [stream, speech_s, final ms, intent ms, parse_ms]: " + json.dumps(
            [[u["stream"], u["speech_s"], firsts(u, "transcript_final"), firsts(u, "intent"),
              next((round(e["stages"].get("parse_ms", 0)) for e in u["events"]
                    if e["type"] == "latency_budget"), None)] for u in utterances]))
        say(f"voice_to_intent ms: n {len(lat)} min {min(lat):.1f} p50 {percentile(lat, 50):.1f} "
            f"mean {sum(lat) / len(lat):.1f} max {max(lat):.1f}")
    if records:
        key = "ms_from_due" if "ms_from_due" in records[0] else "ms"
        lat = [r[key] if r["outcome"] == "plan" else max(r[key], fail_ms) for r in records]
        e2e["parse_p50_ms"], e2e["parse_p95_ms"] = percentile(lat, 50), percentile(lat, 95)
        say(f"/parse {key}: n {len(lat)} p50 {e2e['parse_p50_ms']:.1f} p95 "
            f"{e2e['parse_p95_ms']:.1f} max {max(lat):.1f}")
        if "due_s" in records[0]:
            kept = sum(r["due_s"] + r[key] / 1e3 <= seconds for r in records)
            half = [[r[key] for r in records if (r["due_s"] < seconds / 2) == first] or [0.0]
                    for first in (True, False)]
            say(f"open loop: offered {len(records) / seconds:.3f}/s, answered inside the window "
                f"{kept / seconds:.3f}/s ({kept} of {len(records)}); median from due, requests due in "
                f"the first half {percentile(half[0], 50):.1f} ms, in the second "
                f"{percentile(half[1], 50):.1f} ms")
    return e2e


def read_trace(tracer: Tracer, trace_dir: str, steps: list[dict], chips: int):
    """(reduced trace | None, problem | None)."""
    from tpu_voice_agent.utils.steplog import STAGES

    from benchmark.lib import trace as tr

    path = tr.find_xplane(trace_dir)
    if tracer.error or path is None:
        return None, f"no trace: {tracer.error or 'no .xplane.pb written'}"
    t0 = time.perf_counter()
    reduced = tr.reduce(tr.load_xplane(path), steps, tracer.anchor_wall_s, STAGES, chips)
    if reduced is None:
        return None, "the trace holds no device operation"
    programs = sorted(([k.split("(")[0], v["count"], round(v["total_s"], 6)]
                       for k, v in reduced["programs"].items()), key=lambda r: -r[2])[:8]
    say(f"trace {os.path.getsize(path) / 1e6:.1f} MB reduced in {time.perf_counter() - t0:.1f}s: "
        f"busy {reduced['busy_s']:.6f}s of {reduced['window_s']:.6f}s, anchored "
        f"{reduced['anchored']}, programs [name, runs, device s] {json.dumps(programs)}")
    return reduced, None


def measure(args, cell: dict, rehearsal: bool) -> tuple[dict, int]:
    import jax

    from benchmark.lib import refcheck
    from benchmark.lib.manifest import load_code, load_layer_metric
    from benchmark.lib.stats import histogram, percentile

    client = Client()
    from tpu_voice_agent.utils.compilecache import place_compile_cache

    cache_dir = place_compile_cache()
    chips = cell["cell"]["chips"]
    device, peaks, refusal = open_device(chips, rehearsal)
    if refusal:
        client.close()
        return {}, refuse(refusal)
    compiles: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, _d, **_kw: compiles.append(time.time()) if ev == BACKEND_COMPILE_EVENT else None)
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"cell {args.workload} seed {args.seed} seconds {args.seconds} trace {args.trace}; "
        f"device {device}; jax {jax.__version__}; compile cache {cache_dir} ({n_cached} entries)")

    config, traffic = cell["config"], cell["traffic"]
    served = load_code("builders", config["builder"]).build(config, rehearsal, say)
    try:
        from tpu_voice_agent.utils.compilewatch import get_compile_watcher
        from tpu_voice_agent.utils.steplog import get_steplog

        gen = {"generator": traffic["generator"], "traffic": traffic, "urls": served.urls,
               "seed": args.seed, "seconds": args.seconds}
        warm = client.command(dict(gen, cmd="warm"))
        setup_s = time.perf_counter() - T_PROCESS
        say(f"warm traffic {warm}; setup_s {setup_s:.3f} ({len(compiles)} programs compiled or "
            f"loaded, {len(get_compile_watcher().events())} watched)")

        trace_dir = os.path.join(ROOT, ".bench_trace")
        tracer = None
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracer = Tracer(trace_dir, min(traffic["trace_at_s"], max(0.0, args.seconds - 1.0)),
                            min(traffic["trace_s"], max(0.5, args.seconds / 2)))
            tracer.start()
        reports_rate = any(m["name"] == "out_tokens_per_s" for m in cell["end_to_end"])
        out, edges, watch = run_window(client, gen, tracer, reports_rate)

        t0, t1 = edges["window_start"]["t"], edges["window_end"]["t"]
        window_s = t1 - t0
        peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in jax.devices()[:chips])
        delta = {k: v - edges["window_start"]["counters"].get(k, 0.0)
                 for k, v in edges["window_end"]["counters"].items()}
        steps = [s for s in get_steplog().steps() if t0 <= s["t_s"] <= t1]
        records = [r for r in out.get("records", []) if r["in_window"]]
        utterances = out.get("utterances", [])
        attempted = len(records) + len(utterances)
        failed, problems = judge_parses(records) if "records" in out else judge_utterances(utterances)
        if attempted == 0:
            problems.append("nothing completed inside the window")
        late = out.get("lateness_ms") or [0.0]
        say(f"generator lateness ms: p50 {percentile(late, 50):.2f} p99 {percentile(late, 99):.2f} "
            f"max {max(late):.2f} over {len(late)} sends")

        # the engines stayed healthy, on the kernels, and nothing compiled
        done = delta.get("scheduler.requests_completed", 0.0)
        toks = delta.get("scheduler.tokens_generated", 0.0)
        runtime = served.parser.runtime
        if delta.get("engine.restarts", 0.0) or runtime.stats.restarts or not runtime.healthy():
            problems.append(f"engine restarted or serving loop unhealthy ({runtime.stats.restarts})")
        kernels = [served.engine.kernels] + ([served.stt_engine.kernels] if served.stt_engine else [])
        if not rehearsal and any(k != "pallas" for k in kernels):
            problems.append(f"kernels {kernels}, want pallas")
        in_window = [t for t in compiles if t0 <= t <= t1]
        watched = [e for e in get_compile_watcher().events() if t0 <= e["t_s"] <= t1]
        if watched or in_window:
            problems.append(f"compiled inside the window: {len(watched)} watched "
                            f"{[e['site'] for e in watched][:5]}, {len(in_window)} seen by JAX")
        ends = [s["t_s"] for s in steps]
        waits = [b - a - s["wall_ms"] / 1e3 for a, b, s in zip(ends, ends[1:], steps[1:])]
        say(f"window {window_s:.3f}s: scheduler requests_completed +{done:.0f}, tokens_generated "
            f"+{toks:.0f}, forwards +{delta.get('scheduler.forwards', 0):.0f}, steps {len(steps)} "
            f"(longest {max((s['wall_ms'] for s in steps), default=0.0):.0f} ms, longest wait between "
            f"two {1e3 * max(waits, default=0.0):.0f} ms), "
            f"compiles in window {len(in_window)} (watched {len(watched)}); HBM peak "
            f"{peak_bytes / 1e9:.3f} GB")
        if steps:  # a stall in an untraced run names its stage (PERF.md section 6, "the long step")
            say("longest step of the window, its whole ledger record: "
                + json.dumps(max(steps, key=lambda s: s["wall_ms"])))
        if done:
            chars = [len(json.dumps(r["body"], separators=(",", ":"))) for r in records
                     if r["outcome"] == "plan"]
            say(f"output tokens per request {toks / done:.2f} (counters); plan length histogram, "
                f"characters of the answered JSON: {histogram(chars, [0, 100, 150, 200, 300, 500, 1000])}")

        e2e = end_to_end(records, utterances, traffic, args.seconds, setup_s, toks, window_s, watch)
        ctx = {"records": records, "utterances": utterances, "window_s": window_s,
               "counters": delta, "steps": steps, "peaks": peaks,
               "model": served.dims["model"], "serving": served.dims["serving"],
               "prefix_tokens": len(served.engine.prefix_ids),
               "tokens_per_request": toks / done if done else 0.0, "trace": None}
        dev_out = dict(device, memory_peak_bytes=int(peak_bytes))
        breakdown = None
        if args.trace:
            ctx["trace"], problem = read_trace(tracer, trace_dir, steps, chips)
            if problem:
                problems.append(problem)
            else:
                dev_out.update(busy_s=ctx["trace"]["busy_s"], window_s=ctx["trace"]["window_s"])
                breakdown = {k: ctx["trace"][k] for k in ("device_ops", "idle_gaps")}

        wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
        values: dict = {}
        for m in wanted:
            if args.trace:
                spec = load_layer_metric(m["name"], args.workload)
                v = load_code("readers", spec["reader"]).read(ctx, **spec.get("args", {}))
            else:
                v = e2e.get(m["name"])
            if v is not None:  # a reader that finds nothing returns nothing
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        missing = [m["name"] for m in wanted if m["name"] not in values]
        if missing:
            say(f"metrics with nothing to read: {missing}")
            if not args.trace:
                problems.append(f"end-to-end metrics missing: {missing}")

        # the plain references the configuration names, outside the window and outside setup_s
        for c in refcheck.compare(served, config, args.seed, say):
            if not c["ok"]:
                problems.append(f"the served model disagrees with the plain reference {c['reference']!r}")
        for p in problems[:12]:
            say(f"NOT CORRECT: {p}")
        result = {"correct": not problems and not rehearsal, "attempted": attempted,
                  "failed": failed, "metrics": values, "device": dev_out}
        if breakdown is not None:
            result["breakdown"] = breakdown
        return result, 0
    finally:
        client.close()
        served.close()


def program_env(config: dict) -> None:
    """The program's caches, inside the checkout at fixed paths, and its
    knobs as the configuration files state them — before the program is
    imported (some are read at import)."""
    os.environ["TPU_VOICE_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache", "tpu_voice_cache")
    os.makedirs(os.environ["TPU_VOICE_CACHE_DIR"], exist_ok=True)
    from benchmark.builders.parse_stack import apply_env

    for conf in (config.get("decoder"), config):
        if conf:
            apply_env(conf["serving"])
    os.environ.pop("BENCH_RUN", None)  # the driver's own; nothing here may read it


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "tpu_voice_agent")):
        return refuse("the program (tpu_voice_agent/) is not in this directory")
    from benchmark.lib.manifest import code_problems, load_cell, load_manifest, validate

    manifest = load_manifest()
    bad = validate(manifest)
    if bad:
        return refuse("BENCHMARK.json: " + "; ".join(bad[:5]))
    cell = load_cell(manifest, args.workload)
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    program_env(cell["config"])
    bad = code_problems(cell)  # before any build: a wrong name costs no set-up and no window
    if bad:
        return refuse(f"cell {args.workload} names code that is not there: " + "; ".join(bad[:5]))
    result, rc = measure(args, cell, rehearsal)
    if rc == 0:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # daemon serving threads must not keep the process
