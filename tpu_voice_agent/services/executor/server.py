"""Executor HTTP service (reference: apps/executor/src/server.ts:23-100).

Routes: GET /health, POST /execute, POST /uploads (multipart), POST /close.
Same response envelope as the reference: /execute returns
``{session_id, results[], artifacts: {dir}}``; /uploads returns
``{fileRef: "resume://<id>", path}``.
"""

from __future__ import annotations

import asyncio
import os
import threading
import time

from aiohttp import web

from ...schemas import ExecuteRequest
from ...utils import SLOTracker, Tracer, load_env_cascade, new_trace_id
from ...utils.resilience import (
    AdmissionController,
    Deadline,
    DeadlineExpired,
    shed_response,
)
from .actions import run_intents
from .session import SessionManager


def make_grounder_from_env():
    """EXECUTOR_GROUNDING env -> Grounder | None.

    ``qwen2vl[:preset]`` builds the lazy TPU-backed screenshot grounder
    (serve.grounding.GroundingEngine); unset/empty disables grounding, in
    which case unmatched click targets fall through to the plain text-click
    path exactly as the reference's DOM-only analyzer would
    (apps/executor/src/dom-analyzer.ts:34-448)."""
    spec = os.environ.get("EXECUTOR_GROUNDING", "").strip()
    if not spec:
        return None
    name, _, arg = spec.partition(":")
    if name == "qwen2vl":
        from .grounding import TPUGrounder

        return TPUGrounder(preset=arg or "qwen2vl-7b")
    if name == "qwen2vl-hf":
        # real HF checkpoint directory (config.json + tokenizer.json +
        # safetensors) — BASELINE config 5 with real weights
        if not arg:
            raise ValueError("EXECUTOR_GROUNDING=qwen2vl-hf:<checkpoint dir>")
        from .grounding import TPUGrounder

        return TPUGrounder(model_dir=arg)
    if name == "ground-ckpt":
        # in-tree trained grounding checkpoint (train.ground, orbax layout;
        # default the committed checkpoints/ root)
        from .grounding import TPUGrounder

        return TPUGrounder(ckpt_dir=arg or "checkpoints")
    raise ValueError(f"unknown EXECUTOR_GROUNDING {spec!r}")


def build_app(manager: SessionManager | None = None, tracer: Tracer | None = None,
              grounder=None, summarizer=None,
              max_inflight: int | None = None) -> web.Application:
    manager = manager or SessionManager()
    tracer = tracer or Tracer("executor", emit=False)
    app = web.Application(client_max_size=64 * 1024 * 1024)
    # sessions are single-browser resources; serialize intent batches per proc
    exec_lock = threading.Lock()
    # admission control: batches queue on exec_lock, so past the inflight cap
    # /execute answers 503 + Retry-After rather than growing that queue
    # without bound (the voice service retries on its remaining budget)
    admission = AdmissionController(
        "executor",
        max_inflight if max_inflight is not None
        else int(os.environ.get("EXECUTOR_MAX_INFLIGHT", "16")))

    # per-request /execute latency + error budget against the SLO targets
    slo = SLOTracker("executor")
    # quality observatory (ISSUE 15): action verdicts become weak labels
    # per intent type — the execution-feedback loop the reference never
    # closed (a parse that "succeeded" but whose selector finds nothing is
    # a QUALITY failure, and this is where it becomes measurable)
    from ...utils.quality import QualityMonitor, make_quality_handler

    qmon = QualityMonitor("executor", metrics=tracer.metrics)

    async def health(_req: web.Request) -> web.Response:
        status = "degraded" if admission.saturated else "ok"
        return web.json_response({
            "ok": True, "status": status, "service": "executor",
            "sessions": len(manager.sessions),
            "inflight": admission.inflight,
            "max_inflight": admission.max_inflight,
            "slo": slo.state(),
            "quality": qmon.health(),
        })

    async def execute(req: web.Request) -> web.Response:
        t_req0 = time.perf_counter()
        resp = await _execute_inner(req)
        slo.record((time.perf_counter() - t_req0) * 1e3, ok=resp.status < 500)
        return resp

    async def _execute_inner(req: web.Request) -> web.Response:
        trace_id = req.headers.get("x-trace-id", new_trace_id())
        headers = {"x-trace-id": trace_id}
        try:
            body = await req.json()
        except Exception:
            return web.json_response(
                {"error": "invalid_request", "detail": "body must be JSON"},
                status=400, headers=headers,
            )
        try:
            ereq = ExecuteRequest.model_validate(body)
        except Exception as e:
            return web.json_response(
                {"error": "invalid_request", "detail": str(e)[:500]},
                status=400, headers=headers,
            )

        def shed(reason: str, retry_after_s: float = 1.0) -> web.Response:
            return shed_response("executor", reason, headers=headers,
                                 retry_after_s=retry_after_s)

        deadline = Deadline.from_headers(req.headers)
        if deadline is not None and deadline.expired:
            return shed("deadline_expired", retry_after_s=0)
        if not admission.try_acquire():
            return shed("overload")

        t_q0 = time.perf_counter()

        def work():
            with exec_lock:
                # re-check AFTER winning the lock: the wait may have consumed
                # the caller's whole budget — shed before touching the page
                if deadline is not None and deadline.expired:
                    raise DeadlineExpired("budget consumed waiting for exec_lock")
                session = manager.open(ereq.session_id)
                with tracer.span("execute", trace_id=trace_id, intents=len(ereq.intents),
                                 queue_ms=round((time.perf_counter() - t_q0) * 1e3, 3)):
                    results = run_intents(
                        session.page,
                        session.artifacts_dir,
                        ereq.intents,
                        uploads_dir=manager.uploads_dir,
                        grounder=grounder,
                        summarizer=summarizer,
                    )
                return session, results

        try:
            session, results = await asyncio.get_running_loop().run_in_executor(None, work)
        except DeadlineExpired:
            return shed("deadline_expired", retry_after_s=0)
        except Exception as e:
            return web.json_response(
                {"error": "execution_error", "detail": str(e)[:500]},
                status=500, headers=headers,
            )
        finally:
            admission.release()
        for res in results:
            qmon.record_exec(getattr(res.intent, "type", "unknown"),
                             bool(res.ok))
        return web.json_response(
            {
                "session_id": session.id,
                "results": [r.model_dump() for r in results],
                "artifacts": {"dir": session.artifacts_dir},
            },
            headers=headers,
        )

    async def uploads(req: web.Request) -> web.Response:
        try:
            reader = await req.multipart()
        except Exception:
            return web.json_response(
                {"error": "invalid_request", "detail": "expected multipart/form-data"},
                status=400,
            )
        async for part in reader:
            if part.name in ("file", "upload") or part.filename:
                data = await part.read(decode=False)
                file_ref, path = manager.save_upload(part.filename or "upload.bin", data)
                return web.json_response({"fileRef": file_ref, "path": path})
        return web.json_response(
            {"error": "invalid_request", "detail": "no file part"}, status=400
        )

    async def close(req: web.Request) -> web.Response:
        try:
            body = await req.json()
        except Exception:
            body = {}
        sid = body.get("session_id")

        def work():
            # under exec_lock so a session is never torn down mid-batch
            with exec_lock:
                return manager.close(sid) if sid else False

        ok = await asyncio.get_running_loop().run_in_executor(None, work)
        return web.json_response({"ok": ok})


    app.router.add_get("/health", health)
    from ...utils.tracing import (
        make_flightrecorder_handler,
        make_metrics_handler,
        make_trace_handler,
    )

    app.router.add_get("/metrics", make_metrics_handler("executor", tracer, slo=slo))
    app.router.add_get("/debug/trace/{trace_id}", make_trace_handler("executor", tracer))
    app.router.add_get("/debug/flightrecorder",
                       make_flightrecorder_handler("executor"))
    app.router.add_get("/debug/quality", make_quality_handler(qmon))
    from ...utils.timeseries import attach_timeseries

    attach_timeseries(app, "executor", tracer)
    app.router.add_post("/execute", execute)
    app.router.add_post("/uploads", uploads)
    app.router.add_post("/close", close)
    return app


def model_backends_from_env() -> dict:
    """The executor's optional model backends as ``build_app`` keyword
    arguments (``EXECUTOR_GROUNDING`` / ``EXECUTOR_SUMMARIZE``), each already
    warming in the background: engine construction (checkpoint load + XLA
    compile) can take minutes, and the first grounded click / summarize
    must not stall every session behind exec_lock. Shared by this main and
    the one-process launcher (services.stack)."""
    from .summarize import make_summarizer_from_env

    backends = {"grounder": make_grounder_from_env(),
                "summarizer": make_summarizer_from_env()}
    for backend in backends.values():
        warm = getattr(backend, "warm", None)
        if warm is not None:
            threading.Thread(target=warm, daemon=True).start()
    return backends


def main() -> None:
    load_env_cascade()
    from ...utils.compilecache import place_compile_cache

    place_compile_cache()
    port = int(os.environ.get("EXECUTOR_PORT", "7081"))
    app = build_app(tracer=Tracer("executor"), **model_backends_from_env())
    web.run_app(app, port=port)


if __name__ == "__main__":
    main()
