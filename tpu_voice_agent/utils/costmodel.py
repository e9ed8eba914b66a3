"""Analytic cost model: FLOPs and HBM bytes from config arithmetic,
reconciled against measured walls into MFU/MBU — the hbmledger
discipline applied to *work* instead of *residency*.

``utils/hbmledger`` answers "how many bytes live on the device";
nothing answered "how much work did this chunk do, how close is that
to the hardware roofline, and *who* asked for it". This module closes
all three:

- **The analytic model** (``CostModel``) — FLOPs per prefill token and
  per decode step (attention + MLP matmuls from the config's
  dimensions, MoE-aware: only ``top_k`` experts are active per token),
  KV bytes read/written per step (KV_QUANT-aware via the
  ``ops.kvquant`` per-(position, head) layout — the single
  byte-accounting source), and the Whisper encoder/decoder cost
  (mirrors ``models.whisper.param_count``'s weight walk). Config
  arithmetic only; no device reads, ever.
- **Exact conservation** — every quantity is a Python ``int``. The
  scheduler computes ONE per-row ledger dict per chunk and folds the
  same ints into both the slot's request ledger and the engine meter's
  totals, so ``sum(per-request ledgers) == engine totals`` holds
  *exactly* (bench_cost gates on ``==``, not ``approx``). Float
  reassociation would break that equality; ints cannot.
- **MFU / MBU** (``CostMeter``) — analytic FLOPs (bytes) for a chunk
  divided by the measured chunk wall x the device peak. Peaks come
  from ``COST_PEAK_TFLOPS`` / ``COST_PEAK_GBPS`` when set, else a
  per-``device_kind`` table (TPU generations), else a documented CPU
  proxy so the harness produces finite, stable ratios. Exported as the
  ``engine.mfu`` / ``engine.mbu`` / ``engine.mfu_prefill`` gauges
  (EMA-smoothed) which ride ``/debug/timeseries`` like every gauge.
- **Per-session attribution** (``SessionCostLedger``) — the brain
  folds each ``GenerationResult.cost`` into a per-session LRU so
  ``/debug/costs`` can name the top-cost sessions. This is the meter
  the multi-tenant QoS item fair-shares against.

Ledger keys (all ints):

- ``prefill_flops`` — prompt positions actually computed at admission
- ``prefill_cached_flops`` — FLOPs the prefix/radix cache avoided
  (computed + cached == the full cold-prompt cost, exactly)
- ``decode_flops`` — every decode position computed for the row
- ``decode_bytes`` — KV bytes read + written for those positions
  (weights stream per *dispatch*, batch-shared, and is metered
  engine-side — see ``CostMeter.engine``)
- ``kv_block_us`` — KV block-microseconds held (paged: owned + shared
  blocks x chunk wall; dense: 1 "block" == the slot's KV line)

Everything degrades gracefully off-TPU, like the HBM ledger: the CPU
harness gets exact conservation and stable (proxy-peak) utilization
ratios, which is all the tests and benches need.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict

from . import get_metrics
from .knobs import knob_bool, knob_float, knob_int

LEDGER_KEYS = ("prefill_flops", "prefill_cached_flops", "decode_flops",
               "decode_bytes", "kv_block_us")


def zero_ledger() -> dict:
    return {k: 0 for k in LEDGER_KEYS}


# ------------------------------------------------------------- byte model

def decode_step_bytes(cfg, batch: int, context_tokens: int,
                      kv_quant: str | None = None,
                      weight_quant: str | None = "int8") -> dict:
    """Modeled HBM bytes ONE decode step moves at (batch, context) — the
    CPU-harness proxy for the decode-stage wall (docs/PERF.md: decode is
    HBM-bound, so step wall ∝ bytes moved). Weights stream once per step
    for the whole batch; each live slot reads its attended KV. KV bytes
    follow the ops.kvquant per-(position, head) layout, so the ratio
    between tiers IS the modeled decode-stage speedup the bench kv_quant
    rows report. Hoisted from utils/hbmledger
    (ISSUE 17) so byte accounting has one source of truth beside the
    FLOP model."""
    from ..ops.kvquant import KV_QUANT_VBYTES, KV_SCALE_BYTES

    d, f, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim
    nq, nkv, L, V = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers, cfg.vocab_size
    wbytes = 1 if weight_quant == "int8" else 2
    attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    weights = (L * (attn + _ffn_planes(cfg) * 3 * d * f) + V * d) * wbytes
    per_pos_head = hd * KV_QUANT_VBYTES[kv_quant] + KV_SCALE_BYTES[kv_quant]
    kv = int(2 * L * context_tokens * nkv * per_pos_head) * batch
    return {"weights_bytes": int(weights), "kv_read_bytes": int(kv),
            "total_bytes": int(weights + kv)}


def _ffn_planes(cfg) -> int:
    """SwiGLU planes (gate, up, down of ``ffn_dim``) a layer holds on THIS
    chip: one for a dense MLP; for a routed model the experts HELD here
    (``LlamaConfig.n_held``: all of them, or a chip's share) beside its shared
    experts — a saturated decode step reads nearly all of them. A model that is
    no ``LlamaConfig`` (``models.sambay``) is still counted as a dense decoder
    of its ``ffn_dim`` (PERF.md section 7)."""
    if not getattr(cfg, "n_experts", 0):
        return 1
    return getattr(cfg, "n_held", cfg.n_experts) + getattr(cfg, "n_shared_experts", 0)


def kv_position_bytes(cfg, kv_quant: str | None = None) -> int:
    """Stored KV bytes for ONE token position across all layers (K + V,
    values + scale planes) — the per-position unit both the read term
    (x attended context) and the write term (x positions computed) are
    multiples of. Same kvquant layout as ``decode_step_bytes``."""
    from ..ops.kvquant import KV_QUANT_VBYTES, KV_SCALE_BYTES

    per_pos_head = (cfg.head_dim * KV_QUANT_VBYTES[kv_quant]
                    + KV_SCALE_BYTES[kv_quant])
    return int(2 * cfg.n_layers * cfg.n_kv_heads * per_pos_head)


# ------------------------------------------------------------- FLOP model

def llm_token_flops(cfg) -> int:
    """Weight-matmul FLOPs for ONE token position (prefill or decode —
    the matmul work is identical; attention-vs-context is the separate
    ``llm_attn_flops_per_ctx`` term). 2 FLOPs per MAC over the same
    per-layer matmuls ``hbmledger.engine_hbm_plan`` walks, except MoE:
    the plan counts ALL experts resident, a token only *computes*
    ``top_k`` of them (plus the router). Embedding gather is O(d) and
    deliberately ignored."""
    d, f, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim
    nq, nkv, L, V = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers, cfg.vocab_size
    E = getattr(cfg, "n_experts", 0)
    attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    if E > 0:
        # active experts + router; on a chip that holds a share of the experts
        # a token's picks land here in that proportion, and every token passes
        # through the shared experts
        held = getattr(cfg, "n_held", E)
        ffn = ((getattr(cfg, "top_k", 2) * held // E + getattr(cfg, "n_shared_experts", 0))
               * 3 * d * f + d * E)
    else:
        ffn = 3 * d * f
    return int(2 * (L * (attn + ffn) + V * d))


def llm_attn_flops_per_ctx(cfg) -> int:
    """Attention score + value-mix FLOPs per (token, attended position):
    two hd-MAC dot products per query head, 2 FLOPs per MAC → 4·d."""
    return int(4 * cfg.n_heads * cfg.head_dim)


def prefill_flops(cfg, n_tokens: int, ctx_end: int) -> int:
    """FLOPs to compute the LAST ``n_tokens`` prompt positions of a
    context ending at ``ctx_end`` (causal attention: position p attends
    p + 1 positions). Exact integer arithmetic-series sum, so
    ``prefill_flops(n, n) == prefill_flops(c, c) + (the computed
    remainder)`` holds exactly — the cached-vs-computed split is a
    partition of the cold cost, not an approximation."""
    if n_tokens <= 0:
        return 0
    start = ctx_end - n_tokens  # first computed position index
    # sum of (p + 1) for p in [start, ctx_end): attended positions
    attended = (start + 1 + ctx_end) * n_tokens // 2
    return int(n_tokens * llm_token_flops(cfg)
               + attended * llm_attn_flops_per_ctx(cfg))


def decode_flops(cfg, n_positions: int, ctx: int) -> int:
    """FLOPs for ``n_positions`` decode positions at context ``ctx``
    (end-of-chunk frontier — the model charges every position the full
    attended context rather than integrating within the chunk; both the
    per-row ledger and the engine totals use the same convention, so
    conservation is unaffected)."""
    return int(n_positions * (llm_token_flops(cfg)
                              + ctx * llm_attn_flops_per_ctx(cfg)))


# ---------------------------------------------------------- Whisper model

def whisper_encoder_flops(cfg, n_frames: int) -> int:
    """Encoder FLOPs for ``n_frames`` mel frames. Mirrors
    ``models.whisper.param_count``'s weight walk (conv front-end:
    kernel-3 convs, the second stride-2; per-position QKVO 4·d² +
    FFN 2·d·f) at 2 FLOPs per MAC, plus the full self-attention
    score/mix term over the T = n_frames // 2 output positions."""
    d, f = cfg.d_model, cfg.ffn_dim
    T = max(0, int(n_frames) // 2)
    conv = 2 * (3 * cfg.n_mels * d) * int(n_frames) + 2 * (3 * d * d) * T
    per_pos = 2 * cfg.enc_layers * (4 * d * d + 2 * d * f)
    attn = cfg.enc_layers * 4 * d * T * T
    return int(conv + per_pos * T + attn)


def whisper_decoder_flops(cfg, n_tokens: int, enc_len: int) -> int:
    """Decoder FLOPs for ``n_tokens`` emitted tokens cross-attending
    ``enc_len`` encoder positions. Per token: self-attn QKVO 4·d² +
    cross-attn query/out 2·d² (cross K/V are precomputed once with the
    encoder output) + FFN 2·d·f + logits V·d, x2 FLOPs/MAC, plus the
    cross-attention score/mix reads (4·d per encoder position). The
    short self-attention context (≤ max_text_len) is ignored."""
    d, f = cfg.d_model, cfg.ffn_dim
    per_tok = 2 * (cfg.dec_layers * (6 * d * d + 2 * d * f)
                   + cfg.vocab_size * d)
    cross = cfg.dec_layers * 4 * d * int(enc_len)
    return int(n_tokens * (per_tok + cross))


# ------------------------------------------------------------ device peak

# Published per-chip peaks, keyed by the EXACT ``jax`` ``device_kind``, each
# with its source. THE one peaks table: the live MFU/MBU gauges, bench.py's
# roofline line and chip_smoke.py's device check all read it. A TPU that is
# not in it is an error, not a default.
PEAK_TABLE = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16
        "int8_ops_per_s": 393e12,
        "bytes_per_s": 819e9,       # HBM
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}

# Documented CPU proxy: NOT a hardware claim. A fixed reference point so
# MFU/MBU are finite and comparable run-to-run on the CPU harness (the
# benches gate on ratios and conservation, never on absolute CPU MFU).
_CPU_PROXY = (0.5e12, 50e9)


def device_peak() -> dict:
    """(peak FLOP/s, peak bytes/s) for the local device: knob override >
    ``PEAK_TABLE`` by exact device_kind > the CPU proxy off-accelerator.
    Raises ``KeyError`` for a TPU the table does not know."""
    tflops = knob_float("COST_PEAK_TFLOPS", 0.0)
    gbps = knob_float("COST_PEAK_GBPS", 0.0)
    if tflops > 0 and gbps > 0:
        return {"flops_per_s": tflops * 1e12, "bytes_per_s": gbps * 1e9,
                "device": "knob", "source": "knob"}
    import jax

    dev = jax.devices()[0]
    if dev.platform == "tpu":
        kind = dev.device_kind
        if kind not in PEAK_TABLE:
            raise KeyError(
                f"no published peaks for device_kind {kind!r}: add it to "
                "utils.costmodel.PEAK_TABLE with its source (or set BOTH "
                "COST_PEAK_TFLOPS and COST_PEAK_GBPS)")
        peaks = (PEAK_TABLE[kind]["flops_per_s"], PEAK_TABLE[kind]["bytes_per_s"])
        source = "table"
    else:
        kind, peaks, source = dev.platform, _CPU_PROXY, "cpu-proxy"
    out = {"flops_per_s": peaks[0], "bytes_per_s": peaks[1],
           "device": kind, "source": source}
    if tflops > 0:
        out["flops_per_s"], out["source"] = tflops * 1e12, "knob"
    if gbps > 0:
        out["bytes_per_s"], out["source"] = gbps * 1e9, "knob"
    return out


# ---------------------------------------------------------------- per-row

class CostModel:
    """Per-engine cache of the integer cost constants (the config walk
    runs once, not per chunk). All methods return ints."""

    def __init__(self, cfg, quant: str | None = None,
                 kv_quant: str | None = None) -> None:
        self.cfg = cfg
        self.token_flops = llm_token_flops(cfg)
        self.attn_flops_per_ctx = llm_attn_flops_per_ctx(cfg)
        self.kv_pos_bytes = kv_position_bytes(cfg, kv_quant)
        self.weights_stream_bytes = decode_step_bytes(
            cfg, batch=1, context_tokens=0, kv_quant=kv_quant,
            weight_quant=quant)["weights_bytes"]

    def prefill_split(self, prompt_len: int, cached: int) -> tuple[int, int]:
        """(computed_flops, cached_flops): an exact partition of the
        cold-prompt prefill cost at ``cached`` prefix positions reused."""
        cached = max(0, min(int(cached), int(prompt_len)))
        full = prefill_flops(self.cfg, prompt_len, prompt_len)
        warm = prefill_flops(self.cfg, cached, cached)
        return full - warm, warm

    def decode_row(self, positions: int, ctx: int) -> tuple[int, int]:
        """(flops, bytes) for ``positions`` computed decode positions at
        end-of-chunk context ``ctx``: matmul + attention FLOPs; KV reads
        over the attended context + KV writes for the new positions."""
        positions = int(positions)
        ctx = int(ctx)
        fl = positions * (self.token_flops + ctx * self.attn_flops_per_ctx)
        by = positions * self.kv_pos_bytes * (1 + ctx)
        return int(fl), int(by)


# ------------------------------------------------------------ engine side

_REGISTRY_LOCK = threading.Lock()
_METERS: "OrderedDict[str, CostMeter]" = OrderedDict()


def register_meter(name: str, meter: "CostMeter") -> None:
    with _REGISTRY_LOCK:
        _METERS[name] = meter
        while len(_METERS) > 8:  # bench loops build many engines
            _METERS.popitem(last=False)


_STT_ENGINES: list = []  # weakrefs — bench loops build many engines


def register_stt_engine(engine) -> None:
    """Track a SpeechEngine for the voice-side /debug/costs rollup
    (weakly: a bench-scoped engine must not outlive its bench)."""
    import weakref

    with _REGISTRY_LOCK:
        _STT_ENGINES.append(weakref.ref(engine))
        _STT_ENGINES[:] = [r for r in _STT_ENGINES if r() is not None][-8:]


def stt_cost_summary() -> dict | None:
    """Summed STT encoder/decoder cost across live SpeechEngines (the STT
    share of the observatory). None when nothing registered."""
    with _REGISTRY_LOCK:
        engines = [r() for r in _STT_ENGINES]
    engines = [e for e in engines if e is not None]
    if not engines:
        return None
    out = {"engines": len(engines), "encoder_flops": 0, "decoder_flops": 0,
           "encoded_frames": 0, "decoded_tokens": 0}
    for e in engines:
        for k, v in getattr(e, "cost_totals", {}).items():
            out[k] = out.get(k, 0) + v
    return out


def cost_snapshot() -> dict | None:
    """Flight-dump / bench-artifact body: every registered meter's
    summary, keyed by name (plus the STT share when any SpeechEngine is
    live). None when nothing is metered."""
    with _REGISTRY_LOCK:
        meters = list(_METERS.items())
    out = {name: m.summary() for name, m in meters}
    stt = stt_cost_summary()
    if stt is not None:
        out["stt"] = stt
    return out or None


class CostMeter:
    """Engine-side totals + MFU/MBU. The scheduler folds each row's
    per-chunk ledger here with the SAME int dict it adds to the slot —
    conservation by construction; the bench still catches a dropped or
    double-counted row. Engine-level (non-attributable) lanes — weights
    streamed per dispatch, chunk count — live in ``self.engine``."""

    MFU_EMA = 0.3  # per-chunk smoothing for the exported gauges

    def __init__(self, engine, name: str = "llm") -> None:
        cfg = engine.cfg
        self.model = CostModel(cfg, quant=getattr(engine, "quant", None),
                               kv_quant=getattr(engine, "kv_quant", None))
        self.peak = device_peak()
        self.totals = zero_ledger()
        self.engine = {"weights_stream_bytes": 0, "fwds": 0, "chunks": 0}
        self.mfu = 0.0
        self.mbu = 0.0
        self.mfu_prefill = 0.0
        self._lock = threading.Lock()
        register_meter(name, self)

    def fold_row(self, row: dict) -> None:
        """Fold one row's chunk (or admission) ledger into the totals.
        MUST receive the same dict object the slot accumulates."""
        t = self.totals
        with self._lock:
            for k, v in row.items():
                t[k] += v

    def fold_prefill(self, computed_flops: int, cached_flops: int,
                     compute_ms: float) -> None:
        with self._lock:
            self.totals["prefill_flops"] += int(computed_flops)
            self.totals["prefill_cached_flops"] += int(cached_flops)
        if compute_ms > 0 and computed_flops > 0:
            mfu = computed_flops / (compute_ms / 1e3 * self.peak["flops_per_s"])
            a = self.MFU_EMA
            self.mfu_prefill += a * (mfu - self.mfu_prefill)
            get_metrics().set_gauge("engine.mfu_prefill", self.mfu_prefill)

    def chunk(self, flops: int, kv_bytes: int, fwds: int, wall_s: float) -> None:
        """Per-scheduler-chunk reconciliation: analytic work vs the
        measured chunk wall → EMA'd MFU/MBU gauges + cost.* counters."""
        wbytes = int(fwds) * self.model.weights_stream_bytes
        with self._lock:
            self.engine["weights_stream_bytes"] += wbytes
            self.engine["fwds"] += int(fwds)
            self.engine["chunks"] += 1
        m = get_metrics()
        if flops > 0:
            m.inc("cost.decode_flops", float(flops))
        if kv_bytes > 0:
            m.inc("cost.decode_bytes", float(kv_bytes))
        if wall_s > 0:
            a = self.MFU_EMA
            mfu = flops / (wall_s * self.peak["flops_per_s"])
            mbu = (kv_bytes + wbytes) / (wall_s * self.peak["bytes_per_s"])
            self.mfu += a * (mfu - self.mfu)
            self.mbu += a * (mbu - self.mbu)
            m.set_gauge("engine.mfu", self.mfu)
            m.set_gauge("engine.mbu", self.mbu)

    def summary(self) -> dict:
        with self._lock:
            totals = dict(self.totals)
            engine = dict(self.engine)
        mdl = self.model
        return {
            "totals": totals,
            "engine": engine,
            "mfu": round(self.mfu, 6),
            "mbu": round(self.mbu, 6),
            "mfu_prefill": round(self.mfu_prefill, 6),
            "peak": self.peak,
            "model": {"token_flops": mdl.token_flops,
                      "attn_flops_per_ctx": mdl.attn_flops_per_ctx,
                      "kv_pos_bytes": mdl.kv_pos_bytes,
                      "weights_stream_bytes": mdl.weights_stream_bytes},
        }


def cost_enabled() -> bool:
    return knob_bool("COST_ENABLE")


# ----------------------------------------------------------- session side

class SessionCostLedger:
    """Per-session rollup LRU (brain-side). ``fold`` takes a finished
    request's ``GenerationResult.cost`` dict; ``top`` names the heaviest
    sessions by total FLOPs — the multi-tenant QoS meter."""

    def __init__(self, cap: int | None = None) -> None:
        self.cap = cap if cap is not None else knob_int("COST_SESSIONS")
        self._lock = threading.Lock()
        self._sessions: "OrderedDict[str, dict]" = OrderedDict()

    def fold(self, session_id: str | None, cost: dict | None) -> None:
        if not cost:
            return
        key = session_id or "_stateless"
        with self._lock:
            ent = self._sessions.get(key)
            if ent is None:
                ent = dict(zero_ledger(), utterances=0, last_s=0.0)
                self._sessions[key] = ent
            for k in LEDGER_KEYS:
                ent[k] += int(cost.get(k, 0))
            ent["utterances"] += 1
            ent["last_s"] = round(time.time(), 3)
            self._sessions.move_to_end(key)
            while len(self._sessions) > self.cap:
                self._sessions.popitem(last=False)

    def top(self, n: int = 8) -> list[dict]:
        with self._lock:
            items = [dict(v, session=k) for k, v in self._sessions.items()]
        items.sort(key=lambda e: e["prefill_flops"] + e["decode_flops"],
                   reverse=True)
        return items[:n]

    def snapshot(self) -> dict[str, dict]:
        """All entries, keyed as folded (tenant ledgers key by class name)."""
        with self._lock:
            return {k: dict(v) for k, v in self._sessions.items()}

    def __len__(self) -> int:
        with self._lock:
            return len(self._sessions)
