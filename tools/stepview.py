#!/usr/bin/env python
"""Engine step-ledger timeline viewer.

The scheduler records every chunk's wall-time decomposition into a bounded
ring (utils/steplog.py) served at ``GET /debug/steplog`` on the brain and
folded into flight-recorder freezes. This tool renders that ring as a text
timeline: one gantt row per step, the five tiling stages (admit / prefill /
decode / readback / release) as proportional bar segments, batch
occupancy + token counts — and, where the record holds them, the head of
the step, the gap before it, time off the CPU and collections — in the
margin, a stall snapshot's batcher frames, and any compile-sentinel events
flagged inline on the step that paid the trace — the "why did THIS chunk
take 400 ms" view the per-utterance waterfall (traceview) cannot answer.

Usage:
    python tools/stepview.py [--brain URL] [--json] [--width N] [--last K]
    python tools/stepview.py --file DUMP [--json] [--width N] [--last K]
    python tools/stepview.py --self-test

``--file`` reads a saved ``/debug/steplog`` body OR a flight-recorder dump
(the ``steplog`` section frozen at the incident). ``--self-test`` runs the
render pipeline on a synthetic ring (no services needed) — wired into
tier-1 via tests/test_steplog.py.

Zero dependencies beyond the stdlib: this must work from an operator shell
with nothing installed.
"""

from __future__ import annotations

import argparse
import json
import sys
import urllib.error
import urllib.request

DEFAULT_BRAIN = "http://127.0.0.1:8090"

# the tiling stage order (mirrors utils.steplog.STAGES) and one glyph per
# stage so a bar reads without color
STAGE_GLYPHS = (
    ("admit", "a"),
    ("prefill", "P"),
    ("decode", "█"),
    ("readback", "r"),
    ("release", "·"),
)


def fetch_steplog(base_url: str, timeout_s: float = 5.0) -> dict:
    url = f"{base_url.rstrip('/')}/debug/steplog"
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as r:
            return json.loads(r.read().decode())
    except (urllib.error.URLError, OSError, ValueError) as e:
        print(f"[stepview] {url}: {e}", file=sys.stderr)
        return {}


def load_dump(path: str) -> dict:
    """A saved /debug/steplog body, or a flight-recorder dump carrying a
    ``steplog`` section (the incident-moment ring)."""
    body = json.loads(open(path).read())
    if "steps" not in body and isinstance(body.get("steplog"), dict):
        return body["steplog"]
    return body


def render_step(rec: dict, width: int = 48, max_wall_ms: float | None = None) -> str:
    """One gantt row: seq, wall, the stage bar (segments proportional to
    their share of the step wall, scaled against the window's longest step
    so slow chunks LOOK slow), occupancy/tokens, compile events."""
    wall = max(rec.get("wall_ms", 0.0), 1e-9)
    scale = wall / max(max_wall_ms or wall, 1e-9)
    bar_w = max(1, int(round(width * scale)))
    stages = rec.get("stages", {})
    bar = ""
    used = 0
    for stage, glyph in STAGE_GLYPHS:
        ms = stages.get(stage, 0.0)
        if ms <= 0:
            continue
        n = int(round(bar_w * ms / wall))
        n = min(n, bar_w - used)
        bar += glyph * n
        used += n
    bar = bar.ljust(bar_w)
    meta = []
    if rec.get("occupancy") is not None:
        meta.append(f"occ {rec['occupancy']}")
    if rec.get("tokens") is not None:
        meta.append(f"tok {rec['tokens']}")
    if rec.get("forwards"):
        meta.append(f"fwd {rec['forwards']}")
    # what held the thread (ISSUE 36), where the record says: the head of the
    # step, the gap before it, time off the CPU beside others' CPU, collections
    if "head_ms" in rec:
        meta.append(f"head {rec['head_ms']:.1f}")
    if rec.get("gap_ms"):
        meta.append(f"gap {rec['gap_ms']:.1f}")
    if "cpu_ms" in rec:
        off = sum(stages.values()) - sum(rec["cpu_ms"].values())
        meta.append(f"off-cpu {max(off, 0.0):.1f} (others {sum(rec.get('others_cpu_ms', {}).values()):.1f})")
    if rec.get("gc_n"):
        meta.append(f"gc {rec['gc_n']}x {rec.get('gc_ms', 0.0):.1f} max {rec.get('gc_max_ms', 0.0):.1f}")
    line = (f"#{rec.get('seq', '?'):>5} {rec.get('wall_ms', 0.0):>9.2f} ms "
            f"|{bar}| {' '.join(meta)}")
    stall = rec.get("stall")
    if stall:
        frames = [f for t in stall.get("threads", []) if t.get("name") == stall.get("batcher")
                  for f in t.get("frames", [])]
        line += (f"\n       ⏸ stall snapshot at {stall.get('age_ms', 0.0):.0f} ms "
                 f"(late {stall.get('late_ms', 0.0):.1f}, gc open {stall.get('gc_open_ms')}): "
                 f"spans {stall.get('open_spans')}; batcher {' < '.join(frames[:3])}")
        if "dump_n" in stall:  # the stamp's half (ISSUE 52): when it fired, and who else stood where
            others = [f"{t.get('name')}: {t['frames'][0]}" for t in stall.get("threads", [])
                      if t.get("name") != stall.get("batcher") and t.get("frames")]
            line += (f"\n         sampled {stall['dump_n']}x at {stall.get('dump_at_ms')} ms of the step; "
                     f"run delay {rec.get('run_delay_ms')} throttled {rec.get('throttled_ms')} "
                     f"majflt {rec.get('majflt')}; {' | '.join(others[:4])}")
    for ev in rec.get("events") or []:
        flag = "POST-FENCE " if ev.get("post_fence") else ""
        line += (f"\n       ⚡ {flag}compile {ev.get('site')} "
                 f"{ev.get('ms', 0.0):.0f} ms  {ev.get('shape', '')}")
    return line


def render_timeline(body: dict, width: int = 48, last: int = 0) -> str:
    steps = body.get("steps", [])
    if last > 0:
        steps = steps[-last:]
    if not steps:
        return "(no steps recorded)"
    head = (f"step ledger: {len(steps)} of {body.get('recorded', '?')} "
            f"recorded steps (ring {body.get('max_steps', '?')}, "
            f"enabled={body.get('enabled', '?')})")
    legend = "  ".join(f"{g}={s}" for s, g in STAGE_GLYPHS)
    max_wall = max(s.get("wall_ms", 0.0) for s in steps)
    rows = [render_step(s, width=width, max_wall_ms=max_wall) for s in steps]
    stalls = sum(len(s.get("events") or []) for s in steps)
    foot = f"{stalls} compile stall(s) in window" if stalls else ""
    return "\n".join([head, legend, *rows] + ([foot] if foot else []))


# ------------------------------------------------------------ self-test


def _synthetic_ring() -> dict:
    steps = [
        {"seq": 0, "wall_ms": 412.0, "occupancy": 1, "tokens": 8,
         "stages": {"admit": 2.0, "prefill": 60.0, "decode": 340.0,
                    "readback": 8.0, "release": 2.0},
         "events": [{"site": "engine.chunk_decode_loop", "ms": 310.0,
                     "shape": "int32[4]", "post_fence": True}]},
        {"seq": 1, "wall_ms": 101.0, "occupancy": 3, "tokens": 24,
         "forwards": 8,
         "stages": {"admit": 0.5, "prefill": 12.0, "decode": 80.0,
                    "readback": 6.0, "release": 2.5},
         "cpu_ms": {"admit": 0.4, "prefill": 11.0, "decode": 3.0,
                    "readback": 0.1, "release": 2.0},
         "others_cpu_ms": {"admit": 0.1, "prefill": 0.5, "decode": 2.0,
                           "readback": 0.3, "release": 0.4},
         "head_ms": 0.4, "gap_ms": 3.2, "lock_wait_ms": 0.0,
         "gc_n": 2, "gc_ms": 1.5, "gc_max_ms": 1.2, "watchdog_late_ms": 0.1,
         "gc": [{"gen": 1, "ms": 1.2, "own": False, "at_ms": -2.0}],
         "stall": {"batcher": "colocate", "age_ms": 1030.0, "late_ms": 0.2,
                   "gc_open_ms": None, "open_spans": ["sched.readback"],
                   "threads": [{"name": "colocate",
                                "frames": ["scheduler.py:1297 _step", "scheduler.py:1060 step"]}]}},
        {"seq": 2, "wall_ms": 96.0, "occupancy": 3, "tokens": 24,
         "stages": {"decode": 88.0, "readback": 6.0, "release": 2.0}},
        {"seq": 3, "wall_ms": 400.0, "stages": {"readback": 400.0}, "run_delay_ms": 0.4,
         "majflt": 0, "watchdog_late_ms": 290.0, "stall_dump_n": 1,
         "stall": {"batcher": "colocate", "dump_n": 1, "dump_at_ms": 101.9,
                   "threads": [{"name": "colocate", "frames": ["scheduler.py:1301 _step"]},
                               {"name": "drill-hold", "frames": ["host_wait_check.py:120 hold"]}]}},
    ]
    return {"enabled": True, "max_steps": 256, "recorded": 4, "steps": steps}


def rows_of(txt: str) -> list[str]:
    return [ln for ln in txt.splitlines() if ln.lstrip().startswith("#")]


def self_test() -> int:
    body = _synthetic_ring()
    txt = render_timeline(body, width=40)
    assert "step ledger: 4 of 4" in txt, txt
    assert "POST-FENCE compile engine.chunk_decode_loop" in txt, txt
    assert "⚡" in txt and "1 compile stall(s)" in txt, txt
    assert "occ 3" in txt and "tok 24" in txt and "fwd 8" in txt, txt
    assert "head 0.4 gap 3.2 off-cpu 84.5 (others 3.3) gc 2x 1.5 max 1.2" in txt, txt
    assert "stall snapshot at 1030 ms" in txt and "scheduler.py:1297 _step <" in txt, txt
    # the sampler's half of a stall: when it fired, and the thread that held the interpreter
    assert "sampled 1x at 101.9 ms of the step; run delay 0.4 throttled None majflt 0; " \
           "drill-hold: host_wait_check.py:120 hold" in txt, txt
    assert "off-cpu" not in rows_of(txt)[2]  # a record of an older ledger renders as before
    # the bar scales against the window's longest step: the 412 ms step's
    # bar must be strictly longer than the 96 ms step's
    rows = rows_of(txt)
    assert len(rows) == 4, rows
    w0 = rows[0].split("|")[1]
    w2 = rows[2].split("|")[1]
    assert len(w0.rstrip()) > len(w2.rstrip()), (w0, w2)
    # every recorded stage appears as its glyph somewhere in the bars
    assert "P" in w0 and "█" in w0 and "r" in rows[1].split("|")[1]
    # stage tiling sanity on the synthetic data itself (the ledger's
    # ≥95%-accounted contract, held by the real scheduler tests too)
    for s in body["steps"]:
        assert sum(s["stages"].values()) / s["wall_ms"] >= 0.95
    # --last trims, flight-dump unwrap finds the nested ring
    assert render_timeline(body, last=1).count("#") == 1
    assert render_timeline({"steps": []}) == "(no steps recorded)"
    import json as _json
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        _json.dump({"frozen": True, "steplog": body}, f)
    assert load_dump(f.name)["recorded"] == 4
    print(txt)
    print("stepview self-test ok")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--brain", default=DEFAULT_BRAIN)
    ap.add_argument("--file", metavar="DUMP",
                    help="saved /debug/steplog body or flight dump")
    ap.add_argument("--json", action="store_true", help="JSON instead of gantt")
    ap.add_argument("--width", type=int, default=48)
    ap.add_argument("--last", type=int, default=0,
                    help="only the most recent K steps")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)

    if args.self_test:
        return self_test()
    body = load_dump(args.file) if args.file else fetch_steplog(args.brain)
    if not body:
        return 1
    if args.json:
        if args.last > 0:
            body = dict(body, steps=body.get("steps", [])[-args.last:])
        print(json.dumps(body, indent=1))
        return 0
    print(render_timeline(body, width=args.width, last=args.last))
    return 0


if __name__ == "__main__":
    sys.exit(main())
