"""Run an aiohttp app on a real socket in a background thread (test helper).

Mirrors the reference voice tests' style: boot the actual server on an
ephemeral port and talk to it over TCP (apps/voice/test/server.test.ts:8-14).
The runner itself lives in the package (``services.stack``): the one-process
launcher and ``chip_smoke.py`` serve the stack the same way.
"""

from tpu_voice_agent.services.stack import AppServer

__all__ = ["AppServer"]
