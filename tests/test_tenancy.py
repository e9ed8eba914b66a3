"""Multi-tenant QoS plane (ISSUE 18): registry parsing, weighted fair
shares under a concurrent submit hammer, token-bucket throttling with the
retryable ``shed:`` prefix, chunk-boundary preemption that resumes
token-identically, the requeue aging bound, and the feature-off identity
(TENANT_CLASSES unset => the exact pre-tenancy scheduler paths)."""

import threading
import time

import pytest

from tpu_voice_agent.serve import PagedDecodeEngine
from tpu_voice_agent.serve.scheduler import ContinuousBatcher
from tpu_voice_agent.serve.tenancy import (
    DEFAULT_TENANT,
    FairLanes,
    TenancyPlane,
    parse_tenant_classes,
    tenancy_enabled,
)
from tpu_voice_agent.services.brain import install_prompt_prefix

BUCKETS = (128, 256, 512, 1024, 2048)

PROMPTS = [
    "search for usb hubs", "scroll down", "go back",
    "sort by price", "take a screenshot", "search for keyboards",
]


def _paged(batch_slots=2, radix=True, **kw):
    eng = PagedDecodeEngine(
        preset="test-tiny", max_len=2048, batch_slots=batch_slots,
        prefill_buckets=BUCKETS, radix_enable=radix, **kw)
    install_prompt_prefix(eng)
    return eng


def _batcher(eng, chunk_steps=8, max_new=32):
    return ContinuousBatcher(eng, chunk_steps=chunk_steps,
                             max_new_tokens=max_new)


# ------------------------------------------------------------- registry


def test_parse_tenant_classes_spec():
    classes = parse_tenant_classes(
        "premium:4:slots=3:blocks=64:rps=20:p50=800, free:1:rps=2")
    assert classes["premium"].weight == 4.0
    assert classes["premium"].slots == 3
    assert classes["premium"].blocks == 64
    assert classes["premium"].rps == 20.0
    assert classes["premium"].p50_ms == 800.0
    assert classes["free"].weight == 1.0 and classes["free"].rps == 2.0
    # the implicit default class always exists: unknown tags degrade to
    # shared best-effort, never to a free ride in someone else's lane
    assert classes[DEFAULT_TENANT].weight == 1.0


@pytest.mark.parametrize("bad", [
    "premium:0",            # zero weight
    "premium:1:turbo=9",    # unknown field
    ":2",                   # empty name
    "premium:1:slots",      # field without =
])
def test_parse_tenant_classes_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_tenant_classes(bad)


def test_tenancy_enabled_follows_knob(monkeypatch):
    monkeypatch.delenv("TENANT_CLASSES", raising=False)
    assert not tenancy_enabled()
    monkeypatch.setenv("TENANT_CLASSES", "premium:4")
    assert tenancy_enabled()


# ----------------------------------------------------- plane unit rules


def test_fair_pick_prefers_poorest_lane_with_headroom():
    plane = TenancyPlane(parse_tenant_classes("a:3:slots=1,b:1"))
    plane.charge("a", 30)   # vtime 10
    plane.charge("b", 30)   # vtime 30
    assert plane.pick(["a", "b"]) == 0       # a is poorer
    plane.on_dequeue("a", admitted=True)     # a now holds its 1-slot cap
    assert plane.pick(["a", "b"]) == 1       # capped lane is skipped
    assert plane.pick(["a"]) is None         # every waiter capped


def test_idle_lane_catchup_no_retroactive_credit():
    plane = TenancyPlane(parse_tenant_classes("busy:1,idle:1"))
    plane.on_queue("busy")
    plane.charge("busy", 1000)
    # idle re-enters: its clock jumps to the busy minimum — no banked
    # credit from the time it submitted nothing
    plane.on_queue("idle")
    assert plane.lane("idle").vtime == pytest.approx(1000.0)


def test_fairlanes_rank_composes_before_priority():
    lanes = FairLanes(parse_tenant_classes("premium:4,free:1"))
    lanes.charge("premium", 4.0)  # vtime 1.0
    lanes.charge("free", 4.0)     # vtime 4.0
    assert lanes.rank("premium") < lanes.rank("free")
    assert lanes.rank("unknown") == lanes.rank(None)  # both -> default


# ------------------------------------------------- scheduler integration


def test_feature_off_identity(monkeypatch):
    """THE differential: with TENANT_CLASSES unset the plane is simply not
    constructed, and outputs match the plane-on run token-for-token (greedy
    decode; fair admission may reorder, results must not change)."""
    monkeypatch.delenv("TENANT_CLASSES", raising=False)
    b_off = _batcher(_paged())
    assert b_off.tenancy is None
    off = b_off.generate_many(PROMPTS[:4])

    monkeypatch.setenv("TENANT_CLASSES", "a:2,b:1")
    b_on = _batcher(_paged())
    assert b_on.tenancy is not None
    rids = [b_on.submit(p, tenant=("a" if i % 2 == 0 else "b"))
            for i, p in enumerate(PROMPTS[:4])]
    b_on.run_until_done()
    for r_off, rid in zip(off, rids):
        assert r_off.error is None
        assert b_on.results[rid].token_ids == r_off.token_ids


def test_rate_limited_tenant_sheds_not_errors(monkeypatch):
    """An over-rps burst is refused at submit with the retryable ``shed:``
    prefix (503 + Retry-After at the brain), and only the bucket's share
    decodes — throttled, never errored or queued."""
    monkeypatch.setenv("TENANT_CLASSES", "slowpoke:1:rps=1")
    b = _batcher(_paged())
    rids = [b.submit(PROMPTS[i % len(PROMPTS)], tenant="slowpoke")
            for i in range(5)]
    shed = [r for r in rids if r in b.results]
    assert len(shed) == 4  # burst = max(1, rps) -> exactly one admitted
    for r in shed:
        assert b.results[r].error.startswith("shed: tenant slowpoke")
    b.run_until_done()
    survivor = [r for r in rids if r not in shed]
    assert len(survivor) == 1 and b.results[survivor[0]].error is None
    assert b.tenancy.snapshot()["lanes"]["slowpoke"]["throttled"] == 4


def test_preemption_resumes_warm_and_token_identical(monkeypatch):
    """Chunk-boundary preemption is preempted-NOT-errored: the victim's
    chain is released warm into its tenant's radix namespace, the original
    prompt requeues, and the resumed decode finishes token-identical to an
    uncontended run."""
    monkeypatch.setenv("TENANT_CLASSES", "premium:4,free:1")
    refs = {p: _batcher(_paged(batch_slots=1), max_new=48)
            .generate_many([p])[0] for p in PROMPTS[:2]}
    b = _batcher(_paged(batch_slots=1), max_new=48)
    r_free = b.submit(PROMPTS[0], tenant="free")
    b.step()  # free holds the only slot, one chunk decoded
    r_prem = b.submit(PROMPTS[1], tenant="premium")
    b.run_until_done()
    lanes = b.tenancy.snapshot()["lanes"]
    assert lanes["free"]["preemptions"] >= 1
    for rid, p in ((r_free, PROMPTS[0]), (r_prem, PROMPTS[1])):
        res = b.results[rid]
        assert res.error is None
        assert res.token_ids == refs[p].token_ids


def test_radix_namespaces_are_tenant_salted(monkeypatch):
    """Two tenants decoding the same prompt get separate (salted) radix
    chains; the shared pinned prompt prefix stays one cross-tenant node."""
    monkeypatch.setenv("TENANT_CLASSES", "a:1,b:1")
    eng = _paged()
    b = _batcher(eng)
    # long enough that prompt+generated fills complete blocks — radix
    # chains only adopt full blocks
    ids = eng.tokenizer.encode(PROMPTS[0], bos=True) * 40
    for t in ("a", "b"):
        rid = b.submit(ids, tenant=t)
        b.run_until_done()
        assert b.results.pop(rid).error is None
    rc = eng.radix[0]
    nodes, stack = [], [rc.root]
    while stack:
        n = stack.pop()
        nodes += list(n.children.values())
        stack += list(n.children.values())
    salted = [n for n in nodes if n.ns is not None]
    assert {n.ns for n in salted} == {"a", "b"}
    # same ids, different namespaces: both tenants own their own copy —
    # while the pinned prompt-prefix chain stays ONE cross-tenant node
    assert len(salted) >= 2
    assert any(n.pinned and n.ns is None for n in nodes)


def test_fairness_race_hammer(monkeypatch):
    """Satellite 3: N submitter threads per tenant against a 2-slot
    batcher with preemption on. Zero lost / double-committed requests,
    zero leaked pool blocks, and the decoded-token split over the
    contended window tracks the 3:1 weights within 10 points."""
    monkeypatch.setenv("TENANT_CLASSES", "premium:3,free:1")
    monkeypatch.setenv("SCHED_POOL_WAIT_S", "60")
    eng = _paged(radix=False)  # radix off => idle pool must return to full
    free0 = eng.allocator.free_blocks(0)
    b = _batcher(eng, chunk_steps=8, max_new=16)

    per_thread, threads_per_tenant = 4, 3
    rids: dict[str, list[int]] = {"premium": [], "free": []}
    lock = threading.Lock()

    def submitter(tenant: str) -> None:
        for i in range(per_thread):
            rid = b.submit(PROMPTS[i % len(PROMPTS)], tenant=tenant)
            with lock:
                rids[tenant].append(rid)

    threads = [threading.Thread(target=submitter, args=(t,))
               for t in ("premium", "free") for _ in range(threads_per_tenant)]
    for th in threads:
        th.start()
    # drive the scheduler concurrently with the submitters (the colocate
    # arrangement: submit from request threads, step from the loop)
    deadline = time.monotonic() + 120
    want = per_thread * threads_per_tenant * 2
    contended_share = None
    while time.monotonic() < deadline:
        b.step()
        lanes = b.tenancy.snapshot()["lanes"]
        total = lanes["premium"]["tokens"] + lanes["free"]["tokens"]
        # sample the share while BOTH lanes still have backlog — after the
        # queues drain, equal finite demand converges every split to 1:1
        if (contended_share is None and total >= 96
                and lanes["premium"]["queued"] > 0
                and lanes["free"]["queued"] > 0):
            contended_share = lanes["premium"]["tokens"] / total
        with lock:
            done = all(r in b.results
                       for rs in rids.values() for r in rs)
        if done and not any(s.request_id >= 0 for s in b.slots):
            break
        time.sleep(0)
    for th in threads:
        th.join()

    all_rids = rids["premium"] + rids["free"]
    assert len(all_rids) == want
    # zero lost, zero double-committed: every rid has exactly one result
    # and every result decoded clean
    assert sorted(b.results) == sorted(all_rids)
    for r in all_rids:
        assert b.results[r].error is None, b.results[r].error
    # zero leaked blocks: with radix off, a drained scheduler returns the
    # pool to exactly its initial free count (preemptions included)
    assert eng.allocator.free_blocks(0) == free0
    assert contended_share is not None, "never observed a contended window"
    assert abs(contended_share - 0.75) <= 0.10, contended_share


def test_requeue_rotation_unsticks_small_requests(monkeypatch):
    """Satellite 2 regression: a pool-starved head requeue must rotate to
    the back after SCHED_REQUEUE_MAX retries so small requests behind it
    admit — not starve behind an oversized prompt for the whole pool wait."""
    monkeypatch.delenv("TENANT_CLASSES", raising=False)  # generic bug, plane off
    monkeypatch.setenv("SCHED_POOL_WAIT_S", "60")
    monkeypatch.setenv("SCHED_REQUEUE_MAX", "2")
    from tpu_voice_agent.utils import get_metrics

    eng = _paged(radix=False, pool_blocks=16)
    b = _batcher(eng, chunk_steps=4, max_new=48)
    base = eng.tokenizer.encode(PROMPTS[3], bos=True)
    bs = eng.block_size
    # prefill allocates whole BUCKETS (power-of-two blocks) and the pinned
    # prompt prefix is resident, so size everything off the live pool:
    # big takes the largest bucket the fully-drained pool can still serve
    # (len stays half a block under the bucket so decode never needs a
    # block past it), and the occupant holds just enough that big's bucket
    # cannot fit while it lives — PoolExhausted until the occupant drains
    pool = eng.allocator.free_blocks(0)
    big_blocks = max(n for n in (1, 2, 4, 8, 16) if n <= pool - 1)
    need = big_blocks * bs - bs // 2
    big_ids = (base * (need // len(base) + 1))[:need]
    occ_need = (pool - big_blocks + 1) * bs - bs // 2
    occ_ids = (base * (occ_need // len(base) + 1))[:occ_need]
    occupant = b.submit(occ_ids)
    b.step()  # occupant holds a slot (and its blocks) for ~12 chunks
    big = b.submit(big_ids)
    small = [b.submit(p) for p in PROMPTS[1:3]]
    rot0 = get_metrics().snapshot()["counters"].get(
        "scheduler.requeue_rotations", 0.0)
    admitted: list[int] = [occupant]
    for _ in range(200):
        b.step()
        admitted += [sl.request_id for sl in b.slots
                     if sl.request_id >= 0 and sl.request_id not in admitted]
        if len(b.results) == 4:
            break
    assert len(b.results) == 4, f"stuck: only {list(b.results)} finished"
    for rid in (occupant, big, *small):
        assert b.results[rid].error is None, b.results[rid].error
    # the small requests must be ADMITTED before the oversized head — that
    # is the aging bound working (head yielded after SCHED_REQUEUE_MAX
    # retries). Not the order they FINISH in: big, admitted last into a
    # pool it fills, truncates after a few tokens and is done before the
    # second small request has decoded its 48
    assert all(admitted.index(s) < admitted.index(big) for s in small)
    rot1 = get_metrics().snapshot()["counters"].get(
        "scheduler.requeue_rotations", 0.0)
    assert rot1 > rot0
