"""Brain service contract tests.

Mirrors the reference's apps/brain/test/parse.test.ts:1-101 — valid search
parse, upload+confirmation+tts, follow-up question with low confidence — plus
the error envelopes (400/422/500) against the real HTTP socket.
"""

import httpx
import pytest

from tpu_voice_agent.services.brain import (
    EngineParser,
    ParserError,
    RuleBasedParser,
    build_app,
)
from tests.http_helper import AppServer


@pytest.fixture(scope="module")
def rule_server():
    with AppServer(build_app(RuleBasedParser())) as srv:
        yield srv


def test_health(rule_server):
    r = httpx.get(rule_server.url + "/health")
    assert r.status_code == 200 and r.json()["ok"] is True


def test_parse_search(rule_server):
    r = httpx.post(
        rule_server.url + "/parse",
        json={"text": "search for wireless headphones", "context": {}},
    )
    assert r.status_code == 200
    body = r.json()
    assert body["intents"][0]["type"] == "search"
    assert body["intents"][0]["args"]["query"] == "wireless headphones"
    assert body["context_updates"]["last_query"] == "wireless headphones"
    assert 0 <= body["confidence"] <= 1


def test_parse_upload_requires_confirmation(rule_server):
    r = httpx.post(
        rule_server.url + "/parse",
        json={"text": "upload my resume and submit the form", "context": {}},
    )
    body = r.json()
    assert r.status_code == 200
    assert body["intents"][0]["type"] == "upload"
    assert body["intents"][0]["requires_confirmation"] is True
    assert body["tts_summary"]


def test_parse_gibberish_low_confidence_follow_up(rule_server):
    r = httpx.post(
        rule_server.url + "/parse", json={"text": "florble the wug", "context": {}}
    )
    body = r.json()
    assert body["intents"][0]["type"] == "unknown"
    assert body["confidence"] <= 0.5
    assert body["follow_up_question"]


def test_invalid_request_400(rule_server):
    r = httpx.post(rule_server.url + "/parse", json={"context": {}})
    assert r.status_code == 400
    assert r.json()["error"] == "invalid_request"
    r = httpx.post(
        rule_server.url + "/parse",
        content=b"{not json",
        headers={"content-type": "application/json"},
    )
    assert r.status_code == 400


def test_trace_id_propagates(rule_server):
    r = httpx.post(
        rule_server.url + "/parse",
        json={"text": "go back", "context": {}},
        headers={"x-trace-id": "deadbeef"},
    )
    assert r.headers.get("x-trace-id") == "deadbeef"


class _FailingParser:
    def __init__(self, kind):
        self.kind = kind

    def parse(self, text, context):
        if self.kind == "boom":
            raise RuntimeError("engine fell over")
        raise ParserError(self.kind, "nope")


def test_parser_422_and_500_envelopes():
    with AppServer(build_app(_FailingParser("schema_validation_failed"))) as srv:
        r = httpx.post(srv.url + "/parse", json={"text": "x", "context": {}})
        assert r.status_code == 422 and r.json()["error"] == "schema_validation_failed"
    with AppServer(build_app(_FailingParser("boom"))) as srv:
        r = httpx.post(srv.url + "/parse", json={"text": "x", "context": {}})
        assert r.status_code == 500 and r.json()["error"] == "llm_error"


class _NotingParser:
    """Engine-backend stand-in: deposits the decode split as stage notes
    on the worker thread, like _result_to_response does."""

    def parse(self, text, context):
        from tpu_voice_agent.utils.tracing import note_stage

        note_stage("prefill_ms", 12.5)
        note_stage("decode_ms", 80.25)
        note_stage("cached_tokens", 896)
        note_stage("queue_ms", 1305.25)
        return RuleBasedParser().parse(text, context)


def test_decode_split_rides_response_headers():
    """The prefill/decode/cached-tokens split reaches the caller as
    x-* headers (the voice service folds them into the latency HUD's
    stage breakdown); parsers without notes emit none."""
    with AppServer(build_app(_NotingParser())) as srv:
        r = httpx.post(srv.url + "/parse",
                       json={"text": "search for ants", "context": {}})
        assert r.status_code == 200
        assert r.headers["x-prefill-ms"] == "12.5"
        assert r.headers["x-decode-ms"] == "80.25"
        assert r.headers["x-cached-tokens"] == "896"
        assert r.headers["x-queue-ms"] == "1305.25"
    with AppServer(build_app(RuleBasedParser())) as srv:
        r = httpx.post(srv.url + "/parse",
                       json={"text": "search for ants", "context": {}})
        assert r.status_code == 200
        assert "x-prefill-ms" not in r.headers


def test_one_request_and_token_count_for_both_backends():
    """``brain.parse_completed`` / ``brain.parse_tokens`` move in
    ``_result_to_response``, which the serialized and the batched backend
    share: a decode that ran to its end counts (a truncation too), a typed
    failure does not; the queue wait rides out as a stage note."""
    from tpu_voice_agent.serve.engine import GenerationResult
    from tpu_voice_agent.services.brain import ParserError, _result_to_response
    from tpu_voice_agent.utils import get_metrics
    from tpu_voice_agent.utils.tracing import pop_stage_notes

    def counts():
        c, _ = get_metrics().counter_state()
        return c.get("brain.parse_completed", 0.0), c.get("brain.parse_tokens", 0.0)

    res = lambda **kw: GenerationResult(**{**dict(
        text="{}", token_ids=[], prefill_ms=1.0, decode_ms=2.0, steps=33,
        finished=False, queue_ms=12.3456), **kw})
    n0, t0 = counts()
    with pytest.raises(ParserError):  # truncated: counted, no plan
        _result_to_response(res())
    assert pop_stage_notes()["queue_ms"] == 12.346
    assert counts() == (n0 + 1, t0 + 33)
    with pytest.raises(ParserError):  # shed in the queue: no decode ran
        _result_to_response(res(error="shed: deadline expired in queue", steps=0))
    assert counts() == (n0 + 1, t0 + 33)
    pop_stage_notes()


def test_concurrent_parses_do_not_interleave(rule_server):
    """Racing requests share one parser; the serialization lock must keep
    each response self-consistent."""
    from concurrent.futures import ThreadPoolExecutor

    def post(q):
        return httpx.post(
            rule_server.url + "/parse", json={"text": f"search for {q}", "context": {}}
        ).json()

    with ThreadPoolExecutor(max_workers=4) as ex:
        results = list(ex.map(post, ["ants", "bees", "cats", "dogs"]))
    for q, body in zip(["ants", "bees", "cats", "dogs"], results):
        assert body["intents"][0]["args"]["query"] == q


def test_engine_parser_end_to_end_http(tiny_engine):
    """The full TPU-shaped path over a real socket: HTTP -> prompt render ->
    grammar-constrained decode -> schema-validated ParseResponse."""
    with AppServer(build_app(EngineParser(tiny_engine, max_new_tokens=300))) as srv:
        r = httpx.post(
            srv.url + "/parse",
            json={"text": "search for 4k monitors", "context": {}},
            timeout=180,
        )
        assert r.status_code in (200, 422)  # tiny random weights may truncate
        if r.status_code == 200:
            body = r.json()
            assert "intents" in body and isinstance(body["intents"], list)
        else:
            assert r.json()["error"] == "schema_validation_failed"


@pytest.mark.slow  # compiles the pp×tp pipeline on the 8-device mesh
def test_make_parser_env_routes_pp_backend(monkeypatch):
    """BRAIN_BACKEND=pp[:preset] serves through the TP×PP engine with the
    BRAIN_PP/BRAIN_TP mesh axes (the 70B serving layout's env contract)."""
    from tpu_voice_agent.serve import PPDecodeEngine
    from tpu_voice_agent.services.brain import make_parser_from_env

    monkeypatch.setenv("BRAIN_BACKEND", "pp:test-tiny")
    monkeypatch.setenv("BRAIN_PP", "2")
    monkeypatch.setenv("BRAIN_TP", "2")
    monkeypatch.setenv("BRAIN_BATCH", "2")
    for knob in ("BRAIN_MODEL", "BRAIN_QUANT", "BRAIN_MOE", "BRAIN_PAGED",
                 "BRAIN_PREFIX", "BRAIN_CHUNK", "BRAIN_FF"):
        monkeypatch.delenv(knob, raising=False)
    from tpu_voice_agent.services.brain import ParserError

    parser = make_parser_from_env()
    try:
        assert isinstance(parser.engine, PPDecodeEngine)
        assert parser.engine.pp == 2 and parser.engine.tp == 2
        try:
            resp = parser.parse("go back", {})
            assert resp.version == "1.0"
        except ParserError as e:
            # random weights may ramble to the token budget without EOS —
            # the 422-class truncation envelope is the one legal failure
            assert e.kind == "schema_validation_failed"
    finally:
        parser.close()


def test_speculative_parse_stateless_ok_stateful_409(rule_server):
    """speculative=true is a no-op for stateless parsers (parse is pure)
    but must be refused by session-keyed backends, which would otherwise
    commit a provisional turn to the session transcript."""
    r = httpx.post(rule_server.url + "/parse",
                   json={"text": "search for hubs", "context": {},
                         "speculative": True})
    assert r.status_code == 200
    assert r.json()["intents"][0]["type"] == "search"

    class _SessionParser:
        wants_session = True

        def parse(self, text, context, session_id=None):
            raise AssertionError("speculative parse must not reach a "
                                 "session-keyed backend")

    with AppServer(build_app(_SessionParser())) as srv:
        r = httpx.post(srv.url + "/parse",
                       json={"text": "search for hubs", "session_id": "s",
                             "context": {}, "speculative": True})
        assert r.status_code == 409
        assert r.json()["error"] == "speculation_unsupported"
        # the non-speculative retry goes through to the parser
        r2 = httpx.post(srv.url + "/parse",
                        json={"text": "search for hubs", "session_id": "s",
                              "context": {}})
        assert r2.status_code == 500  # our stub raises AssertionError
