"""Invariant-firewall tests (ISSUE 11, ``tools/analyze``).

Each checker is proven BOTH ways on tmp-tree fixtures — it catches a
seeded violation and stays silent on the clean twin — because a lint that
only has positive tests rots into noise and one that only has negative
tests rots into a no-op. Plus the suppression contract (inline marker,
justification required, baseline round-trip incl. stale detection) and
the tier-1 tree-clean gate: the REAL repo, with its REAL baseline, must
be analyzer-clean on every commit.

All fast-tier: pure AST, no jax import, no services.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))

from tools.analyze import run  # noqa: E402
from tools.analyze import metrics_catalog  # noqa: E402
from tools.analyze.__main__ import main as analyze_main  # noqa: E402


# ------------------------------------------------------------- fixtures


def make_tree(tmp_path: Path, files: dict[str, str]) -> Path:
    for rel, body in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    return tmp_path


def run_only(root: Path, checker: str, baseline: Path | None = None):
    """(live, suppressed) for one checker over a tmp tree. The default
    baseline is a path that does not exist — tmp trees never see the real
    repo's baseline."""
    return run(repo_root=root, baseline=baseline or root / "no_baseline.json",
               only={checker})


def keys(findings) -> set[str]:
    return {f.key for f in findings}


# ----------------------------------------------------------- jit-sentinel


def test_jit_sentinel_catches_unwrapped_def_stored_and_order(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/mod.py": """
        import jax
        from functools import partial
        from .utils.compilewatch import watch_compiles

        @jax.jit
        def naked(x):
            return x

        @partial(jax.jit, static_argnames=("k",))
        def naked_partial(x, k):
            return x

        stored = jax.jit(lambda x: x)

        @jax.jit
        @watch_compiles("mod.inside_out")
        def inside_out(x):
            return x
        """})
    live, _ = run_only(root, "jit-sentinel")
    assert {"naked", "naked_partial", "stored", "inside_out:order"} <= keys(live)


def test_jit_sentinel_passes_wrapped_and_immediate_invoke(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/mod.py": """
        import jax
        from functools import partial
        from .utils.compilewatch import watch_compiles

        @watch_compiles("mod.good")
        @jax.jit
        def good(x):
            return x

        @watch_compiles("mod.good_partial")
        @partial(jax.jit, static_argnames=("k",))
        def good_partial(x, k):
            return x

        stored = watch_compiles("mod.stored")(jax.jit(lambda x: x))
        one_shot = jax.jit(lambda: 0)()  # immediately invoked: init compile
        """})
    live, _ = run_only(root, "jit-sentinel")
    assert live == []


# --------------------------------------------------------- async-blocking


def test_async_blocking_catches_loop_stalls(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/services/svc.py": """
        import time, requests, httpx

        async def handler(engine, fut):
            time.sleep(1)
            requests.get("http://x")
            httpx.post("http://x")
            fut.result()
            engine.generate("prompt")
        """})
    live, _ = run_only(root, "async-blocking")
    assert {"handler:time.sleep", "handler:requests.get", "handler:httpx.post",
            "handler:fut.result", "handler:engine.generate"} <= keys(live)


def test_async_blocking_passes_offload_idiom_and_sync_code(tmp_path):
    root = make_tree(tmp_path, {
        "tpu_voice_agent/services/svc.py": """
        import asyncio, time

        def sync_path(engine):
            time.sleep(0.1)  # not on the loop: no finding
            return engine.generate("p")

        async def handler(loop, engine):
            def work():
                time.sleep(0.1)  # worker thread: the offload idiom
                return engine.generate("p")
            await asyncio.sleep(0)
            return await loop.run_in_executor(None, work)
        """,
        # blocking calls OUTSIDE services/ are out of scope for this checker
        "tpu_voice_agent/serve/eng.py": """
        import time

        async def warmup():
            time.sleep(0.1)
        """})
    live, _ = run_only(root, "async-blocking")
    assert live == []


# --------------------------------------------------------- atomic-section


def test_atomic_section_catches_suspension_and_marker_imbalance(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/services/r.py": """
        # end-atomic-section

        async def mutate(state, q):
            # atomic-section: table-update -- must commit in one loop step
            state["a"] = 1
            await q.put(state)
            state["b"] = 2
            # end-atomic-section

        async def unclosed(state):
            # atomic-section: never-closed -- oops
            state["c"] = 3
        """})
    live, _ = run_only(root, "atomic-section")
    ks = keys(live)
    assert "table-update:await" in ks
    assert "never-closed:unclosed" in ks
    assert any(k.startswith("unopened@") for k in ks)


def test_atomic_section_passes_await_free_region(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/services/r.py": """
        async def mutate(state, q):
            # atomic-section: table-update -- must commit in one loop step
            state["a"] = 1
            state["b"] = 2
            # end-atomic-section
            await q.put(state)
        """})
    live, _ = run_only(root, "atomic-section")
    assert live == []


# --------------------------------------------------------------- env-knob


_KNOBS_HEADER = """
    KNOBS = {}

    def declare(name, default, doc, table=None):
        KNOBS[name] = (default, doc, table)
"""


def test_env_knob_catches_undeclared_undocumented_stale_and_dynamic(tmp_path):
    root = make_tree(tmp_path, {
        "tpu_voice_agent/utils/knobs.py": _KNOBS_HEADER + """
        declare("DOCLESS_KNOB", "1", "declared for PERF but missing its row", table="docs/PERF.md")
        declare("STALE_KNOB", "1", "declared but nothing reads it", table=None)
        """,
        "tpu_voice_agent/mod.py": """
        import os
        a = os.environ.get("UNDECLARED_KNOB")
        b = os.environ.get("DOCLESS_KNOB")
        c = os.getenv(compute_name())
        """,
        "docs/PERF.md": """
        | knob | default | meaning |
        |---|---|---|
        | `ORPHAN_KNOB` | 1 | documented but never declared |
        """})
    live, _ = run_only(root, "env-knob")
    ks = keys(live)
    assert "UNDECLARED_KNOB" in ks
    assert "DOCLESS_KNOB:undocumented" in ks
    assert "STALE_KNOB:unread" in ks
    assert "ORPHAN_KNOB:orphan" in ks
    assert "dynamic-env-read" in ks


def test_env_knob_registry_accessor_is_validated(tmp_path):
    """knobs.get("NAME") call sites resolve NAME against the registry like
    any raw env read — migrating a read to the accessor must not orphan
    the declaration (':unread') or skip validation of the literal."""
    root = make_tree(tmp_path, {
        "tpu_voice_agent/utils/knobs.py": _KNOBS_HEADER + """
        declare("VIA_ACCESSOR", "1", "read only through knobs.get")
        """,
        "tpu_voice_agent/mod.py": """
        from .utils import knobs
        a = knobs.get("VIA_ACCESSOR")
        b = knobs.get("ACCESSOR_UNDECLARED")
        """})
    live, _ = run_only(root, "env-knob")
    ks = keys(live)
    assert "ACCESSOR_UNDECLARED" in ks
    assert "VIA_ACCESSOR:unread" not in ks


def test_knob_accessors_fall_back_to_declared_defaults():
    """The runtime half of the registry: accessors honor the DECLARED
    default when the env is unset (knob_bool regression: it used to
    override the declared default with its own '' fallback)."""
    from tpu_voice_agent.utils import knobs
    assert "STEPLOG_ENABLE" not in __import__("os").environ
    assert knobs.get("STEPLOG_ENABLE") == "1"  # declared default
    assert knobs.knob_bool("STEPLOG_ENABLE") is True
    assert knobs.knob_bool("STEPLOG_ENABLE", default=False) is False  # override
    assert knobs.knob_bool("RADIX_ENABLE") is False  # declared default None
    assert knobs.knob_int("STEPLOG_STEPS") == 256
    with pytest.raises(KeyError):
        knobs.get("NOT_A_DECLARED_KNOB")


def test_env_knob_passes_declared_documented_read_knob(tmp_path):
    root = make_tree(tmp_path, {
        "tpu_voice_agent/utils/knobs.py": _KNOBS_HEADER + """
        declare("GOOD_KNOB", "1", "a documented tunable", table="docs/PERF.md")
        declare("INFRA_KNOB", None, "harness plumbing, deliberately undocumented")
        """,
        "tpu_voice_agent/mod.py": """
        import os
        from .utils import knobs
        a = os.environ.get("GOOD_KNOB")
        b = os.getenv("INFRA_KNOB")
        c = knobs.get("GOOD_KNOB")  # the registry accessor counts as a read
        """,
        "docs/PERF.md": """
        | knob | default | meaning |
        |---|---|---|
        | `GOOD_KNOB` | 1 | a documented tunable |
        """})
    live, _ = run_only(root, "env-knob")
    assert live == []


def test_env_knob_catches_infra_knob_with_doc_row_and_wrong_table(tmp_path):
    root = make_tree(tmp_path, {
        "tpu_voice_agent/utils/knobs.py": _KNOBS_HEADER + """
        declare("INFRA_KNOB", None, "infrastructure", table=None)
        declare("PERF_KNOB", "1", "lives in PERF", table="docs/PERF.md")
        """,
        "tpu_voice_agent/mod.py": """
        import os
        a = os.environ.get("INFRA_KNOB")
        b = os.environ.get("PERF_KNOB")
        """,
        "docs/PERF.md": """
        | knob | default | meaning |
        |---|---|---|
        | `INFRA_KNOB` | - | should not be documented |
        | `PERF_KNOB` | 1 | correctly here |
        """,
        "docs/RESILIENCE.md": """
        | knob | default | meaning |
        |---|---|---|
        | `PERF_KNOB` | 1 | drifted into the wrong doc |
        """})
    live, _ = run_only(root, "env-knob")
    ks = keys(live)
    assert "INFRA_KNOB:infra-documented" in ks
    assert "PERF_KNOB:wrong-table" in ks


# ---------------------------------------------------------- traced-purity


def test_traced_purity_catches_host_nondeterminism(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/mod.py": """
        import os, time
        import jax
        import numpy as np
        from jax import lax

        @jax.jit
        def traced(x):
            t = time.time()
            seed = os.environ.get("SEED")
            n = np.random.rand()
            print("tracing", x)
            return x + t + n

        def body(carry, x):
            time.sleep_val = time.monotonic()
            return carry, x

        def scanned(xs):
            return lax.scan(body, 0, xs)
        """})
    live, _ = run_only(root, "traced-purity")
    ks = keys(live)
    assert "traced:time.time" in ks
    assert "traced:os.environ.get" in ks
    assert "traced:np.random.rand" in ks
    assert "traced:print" in ks
    assert "body:time.monotonic" in ks  # via lax.scan


def test_traced_purity_passes_host_code_and_debug_print(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/mod.py": """
        import time
        import jax

        def host_side():
            return time.time()  # untraced: fine

        @jax.jit
        def traced(x):
            jax.debug.print("step {x}", x=x)  # the traced-safe spelling
            return x * 2
        """})
    live, _ = run_only(root, "traced-purity")
    assert live == []


# -------------------------------------------------------- metrics-catalog


@pytest.fixture
def pinned_off(monkeypatch):
    """Tmp trees register none of the real repo's pinned names — silence
    the pin gate so fixtures test collisions/catalog sync in isolation."""
    ml = metrics_catalog._lint()
    monkeypatch.setattr(ml, "PINNED", {})
    return ml


def test_metrics_catalog_catches_collision_and_two_way_drift(tmp_path, pinned_off):
    root = make_tree(tmp_path, {
        "tpu_voice_agent/mod.py": """
        def record(m):
            m.inc("svc.requests")
            m.set_gauge("svc.requests", 1)  # KIND COLLISION
            m.inc("svc.undocumented")
        """,
        "docs/OBSERVABILITY.md": """
        | name | type | meaning |
        |---|---|---|
        | `svc.requests` | counter | requests |
        | `svc.gone` | gauge | documented but not registered |
        """})
    live, _ = run_only(root, "metrics-catalog")
    ks = keys(live)
    assert "collision:svc.requests" in ks
    assert "catalog:svc.undocumented" in ks
    assert "catalog:svc.gone" in ks


def test_metrics_catalog_passes_synced_tree(tmp_path, pinned_off):
    root = make_tree(tmp_path, {
        "tpu_voice_agent/mod.py": """
        def record(m):
            m.inc("svc.requests")
            m.set_gauge("svc.depth", 2)
        """,
        "docs/OBSERVABILITY.md": """
        | name | type | meaning |
        |---|---|---|
        | `svc.requests` | counter | requests |
        | `svc.depth` | gauge | queue depth |
        """})
    live, _ = run_only(root, "metrics-catalog")
    assert live == []


def test_metrics_catalog_catches_wrong_documented_type(tmp_path, pinned_off):
    root = make_tree(tmp_path, {
        "tpu_voice_agent/mod.py": """
        def record(m):
            m.inc("svc.requests")
        """,
        "docs/OBSERVABILITY.md": """
        | name | type | meaning |
        |---|---|---|
        | `svc.requests` | gauge | documented as the WRONG kind |
        """})
    live, _ = run_only(root, "metrics-catalog")
    assert "catalog:svc.requests" in keys(live)


def test_env_knob_catches_default_drift_and_tolerates_equivalents(tmp_path):
    """A call-site literal default must agree with the declaration (the
    three-copies-of-a-default drift class); numeric/unset-class
    equivalence is tolerated so '2.0' vs 2 is not noise."""
    root = make_tree(tmp_path, {
        "tpu_voice_agent/utils/knobs.py": _KNOBS_HEADER + """
        declare("DRIFTY", "8", "declared 8")
        declare("NUMERIC", "2.0", "declared 2.0")
        declare("OFFISH", None, "declared unset-means-off")
        """,
        "tpu_voice_agent/mod.py": """
        import os
        a = int(os.environ.get("DRIFTY", "0"))   # DRIFT: 0 != 8
        b = float(os.getenv("NUMERIC", 2))       # ok: 2 == 2.0
        c = os.environ.get("OFFISH", "")         # ok: "" == unset class
        """})
    live, _ = run_only(root, "env-knob")
    ks = keys(live)
    assert "DRIFTY:default-drift" in ks
    assert not any(k.startswith(("NUMERIC:", "OFFISH:")) for k in ks)


def test_async_blocking_catches_result_with_timeout(tmp_path):
    """fut.result(timeout=5) parks the loop up to 5 s — the no-args-only
    guard used to let it through."""
    root = make_tree(tmp_path, {"tpu_voice_agent/services/svc.py": """
        async def handler(fut):
            return fut.result(timeout=5)
        """})
    live, _ = run_only(root, "async-blocking")
    assert "handler:fut.result" in keys(live)


def test_unparseable_file_is_a_finding_not_a_silent_pass(tmp_path):
    """tree=None makes every checker skip the file — the suite must emit
    a syntax-error finding or the firewall exits 0 on a broken tree."""
    root = make_tree(tmp_path, {
        "tpu_voice_agent/mod.py": "def broken(:\n",
    })
    live, _ = run_only(root, "jit-sentinel")
    assert any(f.checker == "syntax-error" and f.path.endswith("mod.py")
               for f in live)


def test_metrics_catalog_universal_family_does_not_hide_stale_rows(tmp_path, pinned_off):
    """The tracer's ``{service}.{span}`` histogram normalizes to ``*.*``
    and matches every dotted string — it must not vouch for stale doc rows
    of OTHER kinds, only for span-shaped histogram rows."""
    root = make_tree(tmp_path, {
        "tpu_voice_agent/mod.py": """
        def record(m, service, span):
            m.observe_ms(f"{service}.{span}", 1.0)
        """,
        "docs/OBSERVABILITY.md": """
        | name | type | meaning |
        |---|---|---|
        | `svc.some_span` | histogram | per-span latency (the family's row) |
        | `svc.totally_gone` | gauge | deleted metric whose row rotted |
        """})
    live, _ = run_only(root, "metrics-catalog")
    ks = keys(live)
    assert "catalog:svc.totally_gone" in ks
    assert "catalog:svc.some_span" not in ks


# ------------------------------------------------------------ suppression


_VIOLATION = """
    import jax

    @jax.jit
    def naked(x):
        return x
"""


def test_inline_suppression_with_justification_suppresses(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/mod.py": """
        import jax

        # analyze: ok[jit-sentinel] -- unit-test fixture, not a dispatch site
        @jax.jit
        def naked(x):
            return x
        """})
    live, suppressed = run_only(root, "jit-sentinel")
    assert live == []
    assert keys(suppressed) == {"naked"}


def test_inline_suppression_without_justification_is_a_finding(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/mod.py": """
        import jax

        # analyze: ok[jit-sentinel]
        @jax.jit
        def naked(x):
            return x
        """})
    live, suppressed = run_only(root, "jit-sentinel")
    assert suppressed == []
    assert any(k.endswith(":no-justification") for k in keys(live))
    assert "naked" in keys(live)  # the original finding survives too


def test_inline_suppression_for_other_checker_does_not_apply(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/mod.py": """
        import jax

        # analyze: ok[traced-purity] -- wrong checker id
        @jax.jit
        def naked(x):
            return x
        """})
    live, _ = run_only(root, "jit-sentinel")
    assert "naked" in keys(live)


def test_baseline_round_trip_and_stale_detection(tmp_path):
    root = make_tree(tmp_path, {"tpu_voice_agent/mod.py": _VIOLATION})
    baseline = root / "baseline.json"

    # 1. no baseline: the finding is live
    live, _ = run_only(root, "jit-sentinel", baseline)
    assert keys(live) == {"naked"}

    # 2. a justified baseline entry suppresses it
    baseline.write_text(json.dumps({"suppressions": [
        {"checker": "jit-sentinel", "path": "tpu_voice_agent/mod.py",
         "key": "naked", "justification": "fixture for the round-trip test"},
    ]}))
    live, suppressed = run_only(root, "jit-sentinel", baseline)
    assert live == []
    assert keys(suppressed) == {"naked"}

    # 3. justification-less entries do NOT suppress and are findings
    baseline.write_text(json.dumps({"suppressions": [
        {"checker": "jit-sentinel", "path": "tpu_voice_agent/mod.py",
         "key": "naked", "justification": "   "},
    ]}))
    live, suppressed = run_only(root, "jit-sentinel", baseline)
    assert suppressed == []
    assert "naked" in keys(live)
    assert any("no" in f.message and "justification" in f.message for f in live)

    # 4. an entry that outlived its violation is a stale finding
    (root / "tpu_voice_agent/mod.py").write_text("x = 1\n")
    baseline.write_text(json.dumps({"suppressions": [
        {"checker": "jit-sentinel", "path": "tpu_voice_agent/mod.py",
         "key": "naked", "justification": "now stale"},
    ]}))
    live, _ = run_only(root, "jit-sentinel", baseline)
    assert any(k.startswith("stale:") for k in keys(live))


def test_baseline_key_survives_line_churn(tmp_path):
    """Finding identity is (checker, path, key) with a SYMBOL key — adding
    lines above the violation must not invalidate the suppression."""
    root = make_tree(tmp_path, {"tpu_voice_agent/mod.py": _VIOLATION})
    baseline = root / "baseline.json"
    baseline.write_text(json.dumps({"suppressions": [
        {"checker": "jit-sentinel", "path": "tpu_voice_agent/mod.py",
         "key": "naked", "justification": "churn-stability fixture"},
    ]}))
    live, _ = run_only(root, "jit-sentinel", baseline)
    assert live == []
    src = (root / "tpu_voice_agent/mod.py").read_text()
    (root / "tpu_voice_agent/mod.py").write_text(
        "# pushed\n# down\n# by\n# comments\n" + src)
    live, _ = run_only(root, "jit-sentinel", baseline)
    assert live == []


# --------------------------------------------------------- tree-clean gate


def test_repo_tree_is_analyzer_clean():
    """THE gate: the real repo, real baseline, all six checkers, zero live
    findings. Every suppression in the tree carries a justification (a
    bare marker or justification-less baseline entry would be a live
    finding and fail right here)."""
    live, suppressed = run(repo_root=REPO_ROOT)
    assert live == [], "analyzer findings on the tree:\n" + "\n".join(
        f.format() for f in live)
    assert suppressed, "expected the tree's documented suppressions to apply"


def test_cli_exit_codes(tmp_path):
    assert analyze_main([]) == 0  # the real tree, via the CLI entry point
    root = make_tree(tmp_path, {"tpu_voice_agent/mod.py": _VIOLATION})
    rc = analyze_main(["--root", str(root),
                       "--baseline", str(root / "nope.json")])
    assert rc == 1


def test_cli_module_invocation():
    """`python -m tools.analyze` — exactly what run_all.py and operators
    run — exits 0 on the tree."""
    proc = subprocess.run([sys.executable, "-m", "tools.analyze"],
                          cwd=REPO_ROOT, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_unknown_checker_id_rejected():
    with pytest.raises(SystemExit):
        analyze_main(["--only", "no-such-checker"])
