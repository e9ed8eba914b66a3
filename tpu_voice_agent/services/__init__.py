"""Service-shared aiohttp bits."""

from aiohttp import web

# App flag: cancel in-flight request handlers when their client
# disconnects (aiohttp >= 3.9 made this opt-in at the AppRunner). Brain
# and voice set it — a dead socket must abort its in-flight decode, not
# burn the slot's token budget — and every runner construction site
# (service main()s, the test/bench AppServer) reads it.
HANDLER_CANCELLATION = web.AppKey("handler_cancellation", bool)


def warm_up(backend) -> None:
    """Run a model backend's ``warmup()`` if it has one (engine-backed
    parsers, the engine-backed STT factory): every entry point calls this
    BEFORE it starts listening, so a service that answers /health has
    compiled what a request makes it dispatch. First requests otherwise
    compile inside the serving loops — under the live microphone, and under
    the colocate stall watchdog."""
    warm = getattr(backend, "warmup", None)
    if warm is not None:
        warm()
