"""The load generator's process. It never imports JAX: the parent holds the
chip and serves; this child speaks HTTP / WebSocket to its sockets like any
client would, from one event loop on one thread.

Protocol: the parent writes ONE JSON line per command on stdin
(``{"cmd": "warm" | "run", "generator", "traffic", "urls", "seed",
"seconds"}``) and this process answers with JSON lines on stdout:
``{"ev": "window_start" | "window_end", "t": <time.time()>}`` at the edges
of the measured window and ``{"ev": "done", "result": {...}}`` at the end of
each command. EOF on stdin ends it."""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main() -> int:
    from benchmark.lib.manifest import load_code

    for line in sys.stdin:
        cmd = json.loads(line)
        gen = load_code("generators", cmd["generator"])
        mark = lambda ev: emit({"ev": ev, "t": time.time()})
        try:
            if cmd["cmd"] == "warm":
                result = asyncio.run(gen.warm(cmd["urls"], cmd["traffic"], cmd["seed"]))
            else:
                result = asyncio.run(gen.run(cmd["urls"], cmd["traffic"], cmd["seed"],
                                             float(cmd["seconds"]), mark))
        except Exception as e:  # the parent decides what a failed generator means
            import traceback

            traceback.print_exc()
            emit({"ev": "done", "error": f"{type(e).__name__}: {e}"})
            continue
        emit({"ev": "done", "result": result})
    return 0


if __name__ == "__main__":
    sys.exit(main())
