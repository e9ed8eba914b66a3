"""Multi-model colocation: Whisper + Llama sharing one device/mesh.

SURVEY.md §7 step 6 / hard part (3): two heterogeneous models, bucketed
shapes, interleaved dispatch with STT priority. CPU-only per the test seam
strategy (§4).
"""

import json

import numpy as np
import pytest

from tpu_voice_agent.serve.colocate import ColocatedServing
from tpu_voice_agent.serve.scheduler import ContinuousBatcher
from tpu_voice_agent.serve.stt import SpeechEngine

def _prompt(utterance: str) -> str:
    # short prompt (the full few-shot prompt overflows the tiny engine's
    # 512-token test bucket; grammar constraint holds regardless)
    import json as _json
    user = _json.dumps({"text": utterance, "context": {}}, separators=(",", ":"))
    return f"<|user|>\n{user}\n<|assistant|>\n"



@pytest.fixture(scope="module")
def stt_engine():
    return SpeechEngine(preset="whisper-test", frame_buckets=(100,), max_new_tokens=8)


def _audio(ms: float = 400.0) -> np.ndarray:
    n = int(16_000 * ms / 1000)
    return (0.1 * np.sin(2 * np.pi * 440 * np.arange(n) / 16_000)).astype(np.float32)


def test_colocated_drain_completes_both_lanes(stt_engine, tiny_batch_engine):
    co = ColocatedServing(stt_engine, ContinuousBatcher(tiny_batch_engine, chunk_steps=8,
                                                        max_new_tokens=192))
    stt_futs = [co.submit_stt(_audio()) for _ in range(2)]
    parse_futs = [
        co.submit_parse(_prompt(u))
        for u in ("search for shoes", "scroll down")
    ]
    co.drain(timeout_s=300)
    for f in stt_futs:
        res = f.result(timeout=1)
        assert isinstance(res.text, str) and res.n_frames > 0
    for f in parse_futs:
        res = f.result(timeout=1)
        assert res.error is None
        if res.finished:  # truncated decodes may stop mid-JSON
            json.loads(res.text)  # grammar-constrained => must parse
    assert co.stats.stt_jobs == 2 and co.stats.parse_jobs == 2
    assert co.stats.decode_chunks >= 1


def test_stt_preempts_between_decode_chunks(stt_engine, tiny_batch_engine):
    """An STT job submitted mid-decode must run at the next chunk boundary,
    not after the whole decode finishes (bounded queueing delay)."""
    co = ColocatedServing(stt_engine, ContinuousBatcher(tiny_batch_engine, chunk_steps=4,
                                                        max_new_tokens=64))
    parse_fut = co.submit_parse(_prompt("sort by price low to high"))
    assert co.step()  # admit + first decode chunk
    assert not parse_fut.done()
    stt_fut = co.submit_stt(_audio())
    assert co.step()  # STT lane must clear within this single step
    assert stt_fut.done()
    co.drain(timeout_s=300)
    assert parse_fut.result(timeout=1).error is None
    order = list(co.stats.trace)  # a bounded deque: the last entries
    first_stt = order.index("stt")
    last_chunk = len(order) - 1 - order[::-1].index("chunk")
    assert first_stt < last_chunk  # interleaved, not appended at the end


def test_worker_thread_serves_both(stt_engine, tiny_batch_engine):
    co = ColocatedServing(stt_engine, ContinuousBatcher(tiny_batch_engine, chunk_steps=8,
                                                        max_new_tokens=48))
    co.start()
    try:
        stt_fut = co.submit_stt(_audio(200))
        parse_fut = co.submit_parse(_prompt("go back"))
        assert stt_fut.result(timeout=300).n_frames > 0
        assert parse_fut.result(timeout=300).error is None
    finally:
        co.stop()


def test_abandon_parse_dequeues_without_racing_worker(tiny_batch_engine):
    """A timed-out request must be dequeued (tombstone applied on the worker
    step path) and its orphaned result purged — overload cannot accumulate
    abandoned work. The surviving request still completes."""
    co = ColocatedServing(None, ContinuousBatcher(tiny_batch_engine, chunk_steps=8,
                                                  max_new_tokens=64))
    keep = co.submit_parse(_prompt("search for keyboards"))
    drop = co.submit_parse(_prompt("take a screenshot"))
    co.abandon_parse(drop)
    co.drain(timeout_s=300)
    assert keep.result(timeout=1) is not None
    assert drop.cancelled()
    # nothing left behind: no pending work, no orphaned futures or results
    assert not co.batcher.pending
    assert not co._parse_futs
    assert not co.batcher.results


def test_stt_less_runtime_rejects_stt_jobs(tiny_batch_engine):
    co = ColocatedServing(None, ContinuousBatcher(tiny_batch_engine, chunk_steps=8))
    with pytest.raises(RuntimeError):
        co.submit_stt(_audio())

