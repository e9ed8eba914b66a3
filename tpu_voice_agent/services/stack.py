"""The whole stack in ONE process: voice + brain + executor on real sockets.

A TPU belongs to one process at a time. With ``VOICE_STT=whisper:*`` the
voice service needs the chip for Whisper and with ``BRAIN_BACKEND=engine:*``
the brain needs the same chip for Llama, so on a one-chip machine the three
``python -m tpu_voice_agent.services.<name>`` mains cannot all run: the
second one to touch JAX fails or hangs. This module hosts the same three
aiohttp apps — built by the same ``build_app`` / ``*_from_env`` functions
as the mains — on three sockets inside one process, each on its own event
loop thread; both engines dispatch to the one chip from there.

    VOICE_STT=whisper:whisper-large-v3 BRAIN_BACKEND=engine:tinyllama-1.1b \\
    BRAIN_QUANT=int8 BRAIN_PAGED=1 BRAIN_BATCH=4 EXECUTOR_FAKE_PAGE=1 \\
        python -m tpu_voice_agent.services.stack

The three separate mains stay for CPU / rule-parser / null-STT use and for
hosts with a chip per process. ``chip_smoke.py``, the swarm's local stack
(``tools/swarm.build_local_stack``) and the tests' ``AppServer`` all run on
what is defined here.
"""

from __future__ import annotations

import asyncio
import os
import signal
import threading
from dataclasses import dataclass, field

from aiohttp import web

from . import HANDLER_CANCELLATION, warm_up


class AppServer:
    """One aiohttp app on a real socket, served from a background thread
    with its own event loop. A context manager; ``port=0`` takes an
    ephemeral port (read it back from ``.port`` / ``.url``)."""

    def __init__(self, app: web.Application, host: str = "127.0.0.1",
                 port: int = 0):
        self.app = app
        self.host = host
        self.port: int | None = port or None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None
        self._started = threading.Event()
        self._error: BaseException | None = None

    def __enter__(self) -> "AppServer":
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        if not self._started.wait(timeout=30):
            raise RuntimeError("server failed to start")
        if self._error is not None:
            raise RuntimeError("server failed to start") from self._error
        return self

    def _run(self) -> None:
        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)

        async def start():
            # services that propagate client disconnects into in-flight
            # work (brain/voice mid-decode cancellation, ISSUE 7) set this
            # app flag; aiohttp >= 3.9 made handler cancellation opt-in
            runner = web.AppRunner(
                self.app,
                handler_cancellation=bool(
                    self.app.get(HANDLER_CANCELLATION, False)))
            await runner.setup()
            site = web.TCPSite(runner, self.host, self.port or 0)
            await site.start()
            self.port = runner.addresses[0][1]
            self._runner = runner

        try:
            self._loop.run_until_complete(start())
        except BaseException as e:  # e.g. the port is taken: report, don't hang
            self._error = e
            self._started.set()
            return
        self._started.set()
        self._loop.run_forever()

    @property
    def url(self) -> str:
        host = "127.0.0.1" if self.host in ("0.0.0.0", "") else self.host
        return f"http://{host}:{self.port}"

    def __exit__(self, *exc) -> None:
        async def stop():
            await self._runner.cleanup()

        if self._loop is not None and self._error is None:
            asyncio.run_coroutine_threadsafe(stop(), self._loop).result(timeout=10)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)


@dataclass
class Stack:
    """The served stack: its servers (voice first — shutdown order), their
    urls, and the brain's parser (the engine lives behind it)."""

    voice: AppServer
    executor: AppServer
    brain: AppServer | None  # None when voice points at an external brain_url
    parser: object = None
    voice_cfg: object = None  # the VoiceConfig (its stt_factory holds the STT engine)
    urls: dict = field(default_factory=dict)

    @property
    def servers(self) -> list[AppServer]:
        return [s for s in (self.voice, self.executor, self.brain) if s is not None]

    def close(self) -> None:
        for srv in self.servers:
            srv.__exit__(None, None, None)
        closer = getattr(self.parser, "close", None)
        if closer is not None:
            closer()  # stops the batcher's serving loop + watchdog threads

    def __enter__(self) -> "Stack":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def serve_stack(parser=None, *, brain_url: str | None = None,
                voice_cfg: dict | None = None, manager=None,
                brain_kw: dict | None = None, executor_kw: dict | None = None,
                tracers: dict | None = None, host: str = "127.0.0.1",
                ports: dict | None = None) -> Stack:
    """Serve brain (unless ``brain_url`` names one elsewhere, e.g. a router
    tier), executor and voice from this process and wire voice to the other
    two. ``parser`` is what the brain serves; ``voice_cfg`` are
    ``VoiceConfig`` keyword arguments (``stt_factory`` among them; unset
    fields read the environment as in the voice main); ``manager`` the
    executor's ``SessionManager``. Model backends are warmed before any
    socket opens. ``ports`` maps service name to port (default ephemeral)."""
    from .brain import build_app as build_brain
    from .executor import build_app as build_executor
    from .voice import VoiceConfig, build_app as build_voice

    ports = ports or {}
    tracers = tracers or {}
    started: list[AppServer] = []

    def start(name: str, app: web.Application) -> AppServer:
        srv = AppServer(app, host=host, port=ports.get(name, 0)).__enter__()
        started.append(srv)
        return srv

    try:
        brain = None
        if brain_url is None:
            warm_up(parser)
            brain = start("brain", build_brain(parser, tracers.get("brain"),
                                               **(brain_kw or {})))
            brain_url = brain.url
        executor = start("executor", build_executor(
            manager, tracers.get("executor"), **(executor_kw or {})))
        cfg = VoiceConfig(brain_url=brain_url, executor_url=executor.url,
                          **(voice_cfg or {}))
        warm_up(cfg.stt_factory)
        voice = start("voice", build_voice(cfg, tracers.get("voice")))
    except BaseException:
        for srv in reversed(started):
            srv.__exit__(None, None, None)
        raise
    urls = {"voice": voice.url, "brain": brain_url, "executor": executor.url}
    return Stack(voice=voice, executor=executor, brain=brain, parser=parser,
                 voice_cfg=cfg, urls=urls)


def serve_stack_from_env(*, host: str = "127.0.0.1", ports: dict | None = None,
                         emit: bool = True) -> Stack:
    """The three mains' configuration, in one process: the brain's parser
    from ``BRAIN_BACKEND``/``BRAIN_*``, the voice's STT from ``VOICE_STT``,
    the executor's page/grounding/summarizer from ``EXECUTOR_*``. ``emit``
    turns the per-service JSON span log on (the mains' default)."""
    from ..utils import Tracer
    from .brain import make_parser_from_env
    from .executor.server import model_backends_from_env

    return serve_stack(
        make_parser_from_env(),
        executor_kw=model_backends_from_env(),
        tracers={name: Tracer(name, emit=emit)
                 for name in ("brain", "executor", "voice")},
        host=host, ports=ports)


def main() -> None:
    from ..parallel.multihost import init_multihost
    from ..utils import load_env_cascade
    from ..utils.compilecache import place_compile_cache

    load_env_cascade()
    place_compile_cache()
    init_multihost()
    ports = {"voice": int(os.environ.get("VOICE_PORT", "7072")),
             "brain": int(os.environ.get("BRAIN_PORT", "8090")),
             "executor": int(os.environ.get("EXECUTOR_PORT", "7081"))}
    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    with serve_stack_from_env(host="0.0.0.0", ports=ports) as stack:
        print(f"[stack] serving {stack.urls} from pid {os.getpid()} — open "
              f"{stack.urls['voice']}/", flush=True)
        stop.wait()


if __name__ == "__main__":
    main()
