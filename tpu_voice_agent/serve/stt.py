"""Streaming speech-to-text engine on the in-tree Whisper models.

Replaces the reference's Deepgram live client (apps/voice/src/deepgram.ts).
Design:

- audio accumulates host-side; every `partial_interval_s` of new speech the
  current utterance window is re-transcribed and emitted as a partial
  (the reference's interim_results analog)
- the energy endpointer closes the utterance -> final transcript (replacing
  the fixed 1 s debounce, SURVEY.md §6)
- transcription = mel (matmul STFT) -> encoder (audio-frame buckets) ->
  cross-KV precompute -> greedy on-device decode loop (one dispatch, same
  sync discipline as the intent engine)
"""

from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..audio.endpoint import EnergyEndpointer
from ..audio.mel import MelConfig, log_mel_spectrogram
from ..ops.backend import resolve_kernels
from ..utils.compilewatch import watch_compiles
from ..utils.tracing import get_metrics as _metrics
from ..grammar.intent_grammar import default_tokenizer
from ..models.whisper import (
    PRESETS,
    WhisperConfig,
    compute_cross_kv,
    decoder_forward,
    encoder_forward,
    init_params,
    init_self_cache,
)


@watch_compiles("stt._stt_decode_loop")
@partial(jax.jit, static_argnames=("cfg", "max_new", "eos_id", "pad_id",
                                   "attn_impl", "quality_lanes"),
         donate_argnames=("self_cache",))
def _stt_decode_loop(
    params,
    cfg: WhisperConfig,
    self_cache,
    cross_kv,
    enc_mask,
    bos,  # (B, P) int32 decoder prompt (sot sequence; checkpoint-specific)
    suppress,  # (V,) bool — tokens never sampled (specials/timestamps), or None
    live=None,  # (B,) bool — slots to decode; None = all (the B=1 paths)
    max_new_each=None,  # (B,) int32 per-slot token budget; None = max_new for all
    max_new: int = 64,
    eos_id: int = 2,
    pad_id: int = 0,
    attn_impl: str = "xla",
    quality_lanes: bool = False,
):
    """Greedy decode until EOS, fully on device. ONE implementation for the
    B=1 per-connection paths and the multi-stream batched plane
    (serve.stt_batch): the batched path passes a ``live`` slot mask (dead
    slots park immediately — their rows carry garbage cross-KV) and a
    per-slot ``max_new_each`` budget; every slot stops on its OWN EOS /
    budget / max_text_len while the loop runs until all are done. With
    live=None / max_new_each=None the behavior is exactly the historical
    single-stream loop, so the two planes cannot diverge.

    ``quality_lanes`` (ISSUE 15) additionally accumulates the sampled
    token's logprob per emitted token — (sum, min, first) per row ride the
    same combined readback as the tokens, so STT confidence costs no extra
    transfer and never perturbs the greedy pick (argmax of log_softmax IS
    the argmax). False keeps the lanes as inert zeros.

    The decoder prompt is a (B, P) token block (the in-tree toy tokenizer
    uses a single BOS; real Whisper checkpoints need the
    <|startoftranscript|><|lang|><|task|><|notimestamps|> sequence)."""
    B, P = bos.shape

    def pick(logits):
        with jax.named_scope("sample"):
            if suppress is not None:
                logits = jnp.where(suppress[None, :], -jnp.inf, logits)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if not quality_lanes:
                return tok, jnp.zeros((B,), jnp.float32)
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return tok, jnp.take_along_axis(lsm, tok[:, None], axis=-1)[:, 0]

    pos0 = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None, :], (B, P))
    logits, self_cache = decoder_forward(
        params, cfg, bos, pos0, self_cache, cross_kv, enc_mask, attn_impl=attn_impl
    )
    tok0, lp0 = pick(logits[:, P - 1, :])

    budget = (jnp.full((B,), max_new, jnp.int32) if max_new_each is None
              else max_new_each.astype(jnp.int32))
    done0 = (tok0 == eos_id) | (budget <= 0)
    if live is not None:
        done0 = done0 | ~live
    out = jnp.full((B, max_new), pad_id, dtype=jnp.int32)
    conf0 = (jnp.zeros((B,), jnp.float32),  # logprob sum over emitted
             jnp.full((B,), jnp.inf, jnp.float32),  # logprob min
             jnp.zeros((B,), jnp.float32))  # first emitted token's logprob
    carry0 = (self_cache, tok0, jnp.full((B,), P, jnp.int32), out,
              jnp.zeros((B,), jnp.int32), done0, jnp.zeros((), jnp.int32),
              lp0, conf0)

    def cond(c):
        done, step = c[5], c[6]
        return jnp.logical_and(step < max_new, ~jnp.all(done))

    def body(c):
        cache, cur, pos, out, n, done, step, cur_lp, conf = c
        live = ~done
        out = out.at[jnp.arange(B), jnp.minimum(n, max_new - 1)].set(
            jnp.where(live, cur, out[jnp.arange(B), jnp.minimum(n, max_new - 1)])
        )
        if quality_lanes:
            lp_sum, lp_min, lp_first = conf
            conf = (lp_sum + jnp.where(live, cur_lp, 0.0),
                    jnp.where(live, jnp.minimum(lp_min, cur_lp), lp_min),
                    jnp.where(live & (n == 0), cur_lp, lp_first))
        n = n + live.astype(jnp.int32)
        logits, cache = decoder_forward(
            params, cfg, cur[:, None], pos[:, None], cache, cross_kv, enc_mask,
            attn_impl=attn_impl
        )
        nxt, nxt_lp = pick(logits[:, 0, :])
        pos = jnp.where(live, pos + 1, pos)
        done = done | (nxt == eos_id) | (pos >= cfg.max_text_len - 1) | (n >= budget)
        return (cache, jnp.where(live, nxt, cur), pos, out, n, done, step + 1,
                jnp.where(live, nxt_lp, cur_lp), conf)

    self_cache, _, _, out, n, _, _, _, conf = jax.lax.while_loop(
        cond, body, carry0)
    return out, n, self_cache, conf


def finalize_stt_ids(ids: list[int], conf_row, quality_lanes: bool,
                     final: bool):
    """THE one post-decode tail shared by the B=1 plane (``_decode``) and
    the batched plane (``stt_batch._process``): the ``stt_garble`` chaos
    collapse (finals only — post-decode corruption, latency stays green)
    and the host reduction of one row's conf lanes. Keeping this single
    is part of the two planes' identity contract — a divergence here would
    make them report different confidence for identical audio, which the
    fleet detector would read as a replica quality difference. Returns
    ``(ids, logp_mean, logp_min, logp_first, repetition)``."""
    from ..utils.chaos import chaos_fire
    from ..utils.quality import repetition_score

    if final and ids and chaos_fire("stt_garble"):
        ids = [ids[0]] * len(ids)
    logp_mean = logp_min = logp_first = None
    if quality_lanes and ids:
        lp_sum, lp_min, lp_first = (float(x) for x in conf_row)
        logp_mean = round(lp_sum / len(ids), 4)
        logp_min = round(lp_min, 4) if lp_min != float("inf") else None
        logp_first = round(lp_first, 4)
    rep = round(repetition_score(ids), 4) if ids else None
    return ids, logp_mean, logp_min, logp_first, rep


@dataclass
class TranscribeResult:
    text: str
    encode_ms: float
    decode_ms: float
    n_frames: int
    # ISSUE 15 confidence lanes (None when the quality lanes are off or no
    # token was emitted): per-token logprob mean/min, the first content
    # token's logprob (the no-speech-margin proxy), and the host-side
    # repetition heuristic over the emitted ids
    logp_mean: float | None = None
    logp_min: float | None = None
    logp_first: float | None = None
    repetition: float | None = None


@watch_compiles("stt._append_cross_kv")
@partial(jax.jit, donate_argnames=("buf_k", "buf_v"))
def _append_cross_kv(buf_k, buf_v, new_k, new_v, offset, slot=0):
    """Append one encoded block's cross-KV into the utterance buffer at
    `offset` (encoder frames). ``slot`` addresses the batch axis: 0 for the
    per-connection (L, 1, ...) buffers, the pool slot index for the shared
    (L, S, ...) multi-stream pool (serve.stt_batch). Donated: the update
    happens in place."""
    start = (0, slot, offset, 0, 0)
    return (jax.lax.dynamic_update_slice(buf_k, new_k, start),
            jax.lax.dynamic_update_slice(buf_v, new_v, start))


@dataclass
class IncrementalState:
    """Streaming encoder state: the utterance's accumulated cross-attention
    KV plus host-side frame accounting. Partial transcription cost becomes
    O(new audio): each ~0.5 s block is encoded once (block-local attention
    at its true positions) and only its cross-KV is appended; the decoder
    then runs over the accumulated buffer. Finals still re-encode the whole
    window with full bidirectional attention (exact)."""

    cross_k: jax.Array  # (L, 1, enc_positions, nh, hd)
    cross_v: jax.Array
    enc_len: int = 0  # valid encoder frames
    consumed_frames: int = 0  # mel frames consumed from the utterance buffer
    anchor_frames: int = 0  # buffer frame treated as utterance position 0


class SpeechEngine:
    """Whisper encoder-decoder with audio-length buckets."""

    def __init__(
        self,
        preset: str = "whisper-test",
        cfg: WhisperConfig | None = None,
        seed: int = 0,
        frame_buckets: tuple[int, ...] = (100, 300, 1000, 3000),
        max_new_tokens: int = 64,
        mel_cfg: MelConfig = MelConfig(),
        kernels: str = "auto",  # "auto" | "xla" | "pallas" (flash/decode attention)
        tokenizer=None,  # checkpoint tokenizer; None = in-tree toy vocab
        bos_ids: tuple[int, ...] | None = None,  # decoder prompt (sot sequence)
        init_weights: bool = True,
    ):
        self.kernels = resolve_kernels(kernels)
        base = cfg or PRESETS[preset]
        if tokenizer is None:
            self.tokenizer = default_tokenizer()
            vocab = self.tokenizer.vocab_size
        else:
            self.tokenizer = tokenizer
            vocab = base.vocab_size if cfg is not None else tokenizer.vocab_size
            if vocab < tokenizer.vocab_size:
                raise ValueError(
                    f"model vocab {vocab} < tokenizer vocab {tokenizer.vocab_size}"
                )
        self.cfg = replace(base, vocab_size=vocab)
        self.eos_id = int(self.tokenizer.eos_id)
        self.pad_id = int(self.tokenizer.pad_id)
        self.bos_ids = tuple(bos_ids) if bos_ids else (int(self.tokenizer.bos_id),)
        # greedy decode must never emit specials (real Whisper vocabularies
        # carry hundreds of <|...|> control tokens); EOS stays samplable
        special = getattr(self.tokenizer, "special_ids", None)
        if special:
            sup = np.zeros(vocab, dtype=bool)
            sup[list(special)] = True
            sup[self.eos_id] = False
            self.suppress = jnp.asarray(sup)
        else:
            self.suppress = None
        if mel_cfg.n_mels != self.cfg.n_mels:
            # the mel frontend must feed what the encoder expects (large-v3
            # uses 128 bins, the rest of the family 80)
            from dataclasses import replace as _replace

            mel_cfg = _replace(mel_cfg, n_mels=self.cfg.n_mels)
        self.mel_cfg = mel_cfg
        self.frame_buckets = tuple(b for b in frame_buckets if b <= self.cfg.max_audio_frames)
        if not self.frame_buckets:
            # fail at construction, not as an IndexError mid-stream
            raise ValueError(
                f"no frame bucket in {frame_buckets} fits this config's "
                f"max_audio_frames ({self.cfg.max_audio_frames})")
        self.max_new_tokens = max_new_tokens
        from ..utils.quality import quality_lanes_enabled

        self.quality_lanes = quality_lanes_enabled()
        # STT share of the cost observatory (ISSUE 17): analytic encoder/
        # decoder FLOPs folded per encode dispatch / decode loop — host
        # arithmetic only, voice's /debug/costs reads cost_totals
        from ..utils.costmodel import cost_enabled, register_stt_engine

        self.cost_lanes = cost_enabled()
        self.cost_totals = {"encoder_flops": 0, "decoder_flops": 0,
                            "encoded_frames": 0, "decoded_tokens": 0}
        if self.cost_lanes:
            register_stt_engine(self)
        self.params = (
            jax.jit(partial(init_params, self.cfg))(jax.random.PRNGKey(seed))
            if init_weights else None
        )

    def load_params(self, params) -> None:
        self.params = params

    def warmup(self) -> None:
        """Compile every program streaming transcription dispatches — a
        final at each frame bucket, both incremental block widths, the
        partial decode — so the first live utterance does not pay them
        under the microphone (at whisper-large-v3 widths that is minutes of
        XLA compilation, during which audio only queues)."""
        hop = self.mel_cfg.hop
        for b in self.frame_buckets:
            self.transcribe(np.zeros(b * hop, np.float32))
        st = self.incremental_feed(
            self.incremental_init(),
            np.zeros(3 * self.INC_STEP * hop, np.float32))
        self.incremental_decode(st)

    @property
    def _param_dtype(self):
        """Cache/state dtype rule shared by every decode path: follow the
        params (f32-trained in-tree checkpoints must not round their K/V
        through bf16; bf16 checkpoints keep the cheap cache)."""
        return self.params["decoder"]["tok_emb"].dtype if self.params else jnp.bfloat16

    @classmethod
    def from_hf(cls, model_dir: str, language: str = "en", dtype=jnp.bfloat16, **kw) -> "SpeechEngine":
        """Serve a real HF Whisper checkpoint directory (config.json +
        tokenizer.json + *.safetensors). The decoder prompt becomes the
        checkpoint's <|startoftranscript|><|lang|><|transcribe|>
        <|notimestamps|> sequence and all control tokens are suppressed
        during greedy decode. Replaces apps/voice/src/deepgram.ts:33-45
        with on-device weights."""
        from ..ckpt.hf_import import whisper_config_from_hf, whisper_from_hf_state
        from ..grammar.hf_tokenizer import load_hf_tokenizer

        cfg = whisper_config_from_hf(model_dir)
        tok = load_hf_tokenizer(model_dir)
        bos: list[int] = []
        for name in ("<|startoftranscript|>", f"<|{language}|>", "<|transcribe|>",
                     "<|notimestamps|>"):
            tid = tok.id_of(name)
            if tid is not None:
                bos.append(tid)
        eng = cls(cfg=cfg, tokenizer=tok, bos_ids=tuple(bos) or None,
                  init_weights=False, **kw)
        eng.load_params(whisper_from_hf_state(model_dir, cfg, dtype=dtype))
        return eng

    def _bucket(self, n_frames: int) -> int:
        for b in self.frame_buckets:
            if n_frames <= b:
                return b
        return self.frame_buckets[-1]

    # ------------------------------------------------- cost lanes (ISSUE 17)

    def _fold_encoder_cost(self, n_frames: int) -> None:
        """Analytic encoder FLOPs for one encode dispatch over ``n_frames``
        mel frames (incremental blocks pay their lookback re-encode too —
        the hardware did that work). Host ints + a counter inc; never on
        the device path."""
        if not self.cost_lanes:
            return
        from ..utils import get_metrics
        from ..utils.costmodel import whisper_encoder_flops

        fl = whisper_encoder_flops(self.cfg, n_frames)
        self.cost_totals["encoder_flops"] += fl
        self.cost_totals["encoded_frames"] += int(n_frames)
        get_metrics().inc("cost.stt_encoder_flops", float(fl))

    def _fold_decoder_cost(self, n_tokens: int, enc_len: int) -> None:
        """Analytic decoder FLOPs for one greedy decode loop: ``n_tokens``
        forwards (emitted + BOS prompt) cross-attending ``enc_len``
        encoder positions."""
        if not self.cost_lanes:
            return
        from ..utils import get_metrics
        from ..utils.costmodel import whisper_decoder_flops

        fl = whisper_decoder_flops(self.cfg, n_tokens, enc_len)
        self.cost_totals["decoder_flops"] += fl
        self.cost_totals["decoded_tokens"] += int(n_tokens)
        get_metrics().inc("cost.stt_decoder_flops", float(fl))

    # ------------------------------------------------------ incremental

    # mel frames per incremental encode block (0.5 s) and the re-encoded
    # left context carried for conv/attention continuity at block joins
    INC_STEP = 50
    INC_LOOKBACK = 20

    def anchor_for(self, total_frames: int) -> int:
        """The (even) buffer frame streaming consumption anchors at: at most
        one window back, so retained pre-speech silence cannot spend the
        cross-KV budget. ONE definition shared by the per-connection
        IncrementalState and the batched plane's slot pool — the two
        planes' token-identity contract rests on this rule never
        diverging."""
        return max(0, total_frames - self.cfg.enc_positions) & ~1

    def incremental_init(self, total_frames: int = 0) -> IncrementalState:
        """Fresh streaming state. ``total_frames`` = mel frames already in
        the utterance buffer: consumption anchors at most one window
        (enc_positions mel frames) back, so retained pre-speech silence
        cannot spend the cross-KV budget before speech is reached."""
        L, nh, hd = self.cfg.dec_layers, self.cfg.n_heads, self.cfg.head_dim
        # dynamic_update_slice needs exact dtype agreement with the blocks
        # compute_cross_kv emits (enc_out dtype = params dtype)
        z = jnp.zeros((L, 1, self.cfg.enc_positions, nh, hd), self._param_dtype)
        anchor = self.anchor_for(total_frames)
        return IncrementalState(cross_k=z, cross_v=jnp.zeros_like(z),
                                consumed_frames=anchor, anchor_frames=anchor)

    def _encode_block(self, buf: np.ndarray, anchor_frames: int,
                      consumed_frames: int):
        """Encode ONE INC_STEP block of `buf` at its true utterance offset
        (re-encoding INC_LOOKBACK frames of left context, dropped from the
        output). Returns ``(new_k, new_v, keep)`` — the (L, 1, keep, nh, hd)
        cross-KV slab the caller appends at its own write target. Shared by
        the per-connection IncrementalState path and the multi-stream pool
        (serve.stt_batch) so their per-block numerics are identical by
        construction."""
        hop = self.mel_cfg.hop
        step, lb = self.INC_STEP, self.INC_LOOKBACK
        c = consumed_frames
        start = max(anchor_frames, c - lb)
        n_window = c + step - start  # 50 (anchor block) or 70: two compiles
        audio = buf[start * hop:(c + step) * hop].astype(np.float32)
        mel = log_mel_spectrogram(jnp.asarray(audio), self.mel_cfg)[None, :n_window]
        enc = encoder_forward(self.params, self.cfg, mel,
                              attn_impl=self.kernels,
                              pos_offset=jnp.int32((start - anchor_frames) // 2))
        kv = compute_cross_kv(self.params, self.cfg, enc)
        drop = (c - start) // 2  # lookback outputs: context only
        keep = step // 2
        new_k = jax.lax.dynamic_slice_in_dim(kv["k"], drop, keep, axis=2)
        new_v = jax.lax.dynamic_slice_in_dim(kv["v"], drop, keep, axis=2)
        self._fold_encoder_cost(n_window)
        return new_k, new_v, keep

    def incremental_feed(self, state: IncrementalState, buf: np.ndarray) -> IncrementalState:
        """Encode any complete new INC_STEP blocks of `buf` (the utterance
        audio so far) into the state's cross-KV. Each block re-encodes
        INC_LOOKBACK frames of left context (dropped from the output) so
        the conv frontend and block attention see real history; positions
        are the block's offset from the state's anchor. O(new audio) per
        call; when an utterance outgrows the cross-KV budget the state
        re-anchors on the most recent window (one bounded re-encode burst)
        instead of silently freezing."""
        hop = self.mel_cfg.hop
        step = self.INC_STEP
        total = len(buf) // hop
        while total - state.consumed_frames >= step:
            if state.enc_len + step // 2 > self.cfg.enc_positions:
                state = self.incremental_init(total)
                continue
            c = state.consumed_frames
            new_k, new_v, keep = self._encode_block(buf, state.anchor_frames, c)
            ck, cv = _append_cross_kv(state.cross_k, state.cross_v, new_k, new_v,
                                      jnp.int32(state.enc_len))
            state = IncrementalState(
                cross_k=ck, cross_v=cv,
                enc_len=state.enc_len + keep,
                consumed_frames=c + step,
                anchor_frames=state.anchor_frames,
            )
        return state

    def incremental_decode(self, state: IncrementalState) -> TranscribeResult:
        """Greedy decode over the accumulated cross-KV (one dispatch chain,
        one combined device_get — same sync discipline as transcribe).
        encode_ms is 0: the encode cost was paid incrementally in feed()."""
        valid = jnp.arange(self.cfg.enc_positions)[None, :] < state.enc_len
        return self._decode({"k": state.cross_k, "v": state.cross_v}, valid,
                            state.consumed_frames)

    def _decode(self, cross_kv: dict, enc_mask, n_frames: int,
                final: bool = False) -> TranscribeResult:
        """Shared decode tail: greedy loop over cross-KV -> transcript.
        One combined device_get; used by transcribe() and the streaming
        partial path so the two can never diverge. Decodes at the cross-KV's
        OWN length: a small bucket must not pay cross-attention over the
        full 30 s window per step (at whisper-large dims that is a ~30x
        per-step cross-KV read). The batched plane pads its rows to
        enc_positions to mix ragged buckets in one dispatch — padding is
        masked to exact zeros, and tests/test_stt_batch.py holds the two
        shapes token-identical differentially.

        ``final=True`` (transcribe, i.e. finals/spec_finals) arms the
        ``stt_garble`` chaos point — see ``finalize_stt_ids``, the one
        post-decode tail both planes share."""
        t0 = time.perf_counter()
        # on the profiler's trace one partial or final pass is ``stt.pass``,
        # from the decode loop's dispatch to the readback that ends it
        with jax.profiler.TraceAnnotation("stt.pass", final=int(final),
                                          frames=int(n_frames)):
            cache = init_self_cache(self.cfg, 1, dtype=self._param_dtype)
            bos = jnp.asarray(list(self.bos_ids), dtype=jnp.int32)[None, :]
            out, n, _, conf = _stt_decode_loop(
                self.params, self.cfg, cache, cross_kv, enc_mask, bos, self.suppress,
                max_new=self.max_new_tokens, eos_id=self.eos_id, pad_id=self.pad_id,
                attn_impl=self.kernels, quality_lanes=self.quality_lanes,
            )
            out_h, n_a, conf_h = jax.device_get((out, n, conf))
        n_h = int(n_a[0])
        ids = [int(t) for t in np.asarray(out_h)[0, :n_h]]
        decode_ms = (time.perf_counter() - t0) * 1e3
        self._fold_decoder_cost(n_h + len(self.bos_ids),
                                max(1, int(n_frames) // 2))
        ids, logp_mean, logp_min, logp_first, rep = finalize_stt_ids(
            ids, [np.asarray(x)[0] for x in conf_h], self.quality_lanes,
            final)
        return TranscribeResult(
            text=self.tokenizer.decode(ids).strip(),
            encode_ms=0.0,
            decode_ms=decode_ms,
            n_frames=n_frames,
            logp_mean=logp_mean,
            logp_min=logp_min,
            logp_first=logp_first,
            repetition=rep,
        )

    def _encode_window(self, audio: np.ndarray):
        """Front half of transcribe(): bucket, pad, mel, encode, cross-KV.
        Returns ``(cross_kv, enc_mask, n_frames)``. The batched plane
        (serve.stt_batch) encodes each final through THIS method — one B=1
        dispatch per item, exactly transcribe's lowering — because batched
        (B, T) encoder forwards are not bitwise row-stable on every backend
        (bf16 activations + shape-dependent gemm partitioning), and token
        identity with the B=1 path is a contract, not a best effort. The
        encode is a single dispatch; the batching win lives in the decode
        loop's max_new sequential dispatches."""
        hop = self.mel_cfg.hop
        n_frames = max(1, len(audio) // hop)
        bucket = self._bucket(n_frames)
        want = bucket * hop
        if len(audio) > want:
            audio = audio[-want:]
            n_frames = bucket
        padded = np.zeros(want, dtype=np.float32)
        padded[: len(audio)] = audio
        mel = log_mel_spectrogram(jnp.asarray(padded), self.mel_cfg)[None, :bucket]
        enc_out = encoder_forward(self.params, self.cfg, mel, attn_impl=self.kernels)
        cross_kv = compute_cross_kv(self.params, self.cfg, enc_out)
        valid = jnp.arange(enc_out.shape[1])[None, :] < max(1, n_frames // 2)
        self._fold_encoder_cost(bucket)
        return cross_kv, valid, n_frames

    def transcribe(self, audio: np.ndarray) -> TranscribeResult:
        """audio: float32 mono 16 kHz. Longer than the top bucket -> keep the
        most recent window (streaming semantics)."""
        # encode + decode stay in ONE async dispatch chain with a single
        # combined device_get at the end (inside _decode): a mid-flight
        # block would drain the dispatch pipeline and idle the device
        # behind the host, so encode_ms is dispatch-side.
        t0 = time.perf_counter()
        cross_kv, valid, n_frames = self._encode_window(audio)
        encode_ms = (time.perf_counter() - t0) * 1e3

        res = self._decode(cross_kv, valid, n_frames, final=True)
        return dataclasses.replace(res, encode_ms=encode_ms)


# process-wide saturation aggregate: every live StreamingSTT deposits its
# own (feed_lag_s, buffered_audio_s) here and the GAUGES export the
# aggregate — max lag across streams, summed buffered seconds. Before this,
# every instance wrote the same global gauge name, so concurrent
# connections overwrote each other and the scrape showed whichever stream
# fed last. WeakKey: a closed connection's entry disappears with its STT
# object, no deregistration protocol needed.
_AGG_LOCK = threading.Lock()
_LIVE_STREAMS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _record_stream_gauges(inst, feed_lag_s: float, buffered_s: float) -> None:
    m = _metrics()
    with _AGG_LOCK:
        # publish inside the lock: a preempted thread writing a stale
        # aggregate after a newer one would under-report until the next feed
        _LIVE_STREAMS[inst] = (feed_lag_s, buffered_s)
        vals = list(_LIVE_STREAMS.values())
        m.set_gauge("stt.feed_lag_s", round(max(v[0] for v in vals), 4))
        m.set_gauge("stt.buffered_audio_s", round(sum(v[1] for v in vals), 4))


class StreamingSTT:
    """Utterance-windowed streaming wrapper: feed PCM, get partial/final events.

    Events: ("partial", text) while speech continues; ("spec_final", text)
    when the speaker has paused long enough that the utterance is plausibly
    over (the speculative full-window transcription — downstream may start
    parsing it inside the endpoint window); ("final", text) when the
    endpointer closes the utterance (the 1 s debounce replacement).

    Adaptive early endpoint (VERDICT round-4 next #9 — the fixed window
    had become 97% of the measured CPU e2e): when the consumer reports via
    ``parse_complete(text)`` that the speculative parse of the CURRENT
    speculative transcript finished grammar-complete, and the transcript
    has stayed stable (zero new speech frames — silence is content-frozen
    by construction) through ``early_close_ms`` of trailing silence, the
    utterance closes early instead of waiting out the full window. The
    hysteresis guard is the gap between ``early_close_ms`` and the
    endpointer's spec threshold: at defaults (240 vs 175 ms) the close
    needs 3+ consecutive all-silent 20 ms frames AFTER the speculation,
    and a single supra-threshold frame re-arms everything (staleness keys
    on the monotone speech-frame counter). ``early_closes`` /
    ``window_closes`` expose the rates the bench reports.
    """

    def __init__(
        self,
        engine: SpeechEngine,
        partial_interval_s: float = 0.5,
        endpointer: EnergyEndpointer | None = None,
        incremental: bool = True,
        early_close_ms: float | None = 240.0,
    ):
        self.engine = engine
        self.partial_interval_s = partial_interval_s
        self.endpointer = endpointer or EnergyEndpointer(sample_rate=engine.mel_cfg.sample_rate)
        # incremental=True: partials ride the streaming encoder (O(new
        # audio) per partial instead of re-encoding the whole window —
        # SURVEY.md §7 hard part 2); finals always re-encode exactly
        self.incremental = incremental
        # None disables early close. The default is armed but inert until
        # a consumer actually calls parse_complete — the full window
        # remains the behavior for consumers that never speculate.
        self.early_close_ms = early_close_ms
        self.early_closes = 0
        self.window_closes = 0
        self._inc: IncrementalState | None = None
        self._spec_final: TranscribeResult | None = None
        self._spec_at_speech = -1  # endpointer.total_speech_frames at spec time
        self._parse_done: str | None = None
        # the delivered final's full TranscribeResult (confidence lanes
        # included) — the voice service reads it right after the ("final",
        # text) event to ride confidence on transcript_final (ISSUE 15)
        self.last_final: TranscribeResult | None = None
        self._buf = np.zeros(0, dtype=np.float32)
        self._since_partial = 0.0
        # cumulative processing deficit: feed() wall time in excess of the
        # audio duration it consumed. >0 sustained means transcription is
        # falling behind realtime (frames queue up faster than the model
        # drains them) — the STT-side saturation gauge
        self._feed_lag_s = 0.0

    def reset(self) -> None:
        self._buf = np.zeros(0, dtype=np.float32)
        self._since_partial = 0.0
        self._inc = None
        self._spec_final = None
        self._spec_at_speech = -1
        self._parse_done = None
        self._feed_lag_s = 0.0
        self.endpointer.reset()

    def parse_complete(self, text: str) -> None:
        """Consumer signal: the speculative parse of ``text`` finished and
        was grammar-complete (a constrained decode that returned 200 is
        complete by construction — the FSM only accepts full plans). May be
        called from another thread (the voice service's event loop, the
        bench's spec pool): a single attribute store is atomic under the
        GIL, and feed() re-validates against the current fresh speculative
        transcript before acting, so a stale notification can never close
        an utterance whose content moved on."""
        self._parse_done = text

    # -------------------------------------------------- transcription hooks
    # The multi-stream batched plane (serve.stt_batch.BatchedStreamingSTT)
    # overrides exactly these four methods to route transcription work
    # through the shared STTBatcher; everything else in feed() — endpointer,
    # buffering, staleness, early close — is host-side state both planes
    # share verbatim. The base implementations are the historical inline
    # engine calls, byte-identical to the pre-batching behavior.

    def _start_speculation(self, spoken: int, events: list) -> None:
        """The speaker paused: transcribe the (content-frozen) buffer now so
        the endpoint confirmation only delivers it."""
        self._spec_final = self.engine.transcribe(self._buf)
        self._spec_at_speech = spoken
        if self._spec_final.text:
            events.append(("spec_final", self._spec_final.text))

    def _final_result(self, fresh: bool, spoken: int) -> TranscribeResult | None:
        """The endpoint closed: the exact full-window transcription (the
        fresh speculation when the pause was long enough to have seen one).
        None = deferred (the batched plane delivers the final event once its
        future resolves)."""
        return self._spec_final if fresh else self.engine.transcribe(self._buf)

    def _emit_partial(self, events: list) -> None:
        """Mid-speech partial tick: transcribe the utterance so far."""
        if self.incremental:
            if self._inc is None:
                self._inc = self.engine.incremental_init(
                    len(self._buf) // self.engine.mel_cfg.hop)
            self._inc = self.engine.incremental_feed(self._inc, self._buf)
            if self._inc.enc_len > 0:
                res = self.engine.incremental_decode(self._inc)
                if res.text:
                    events.append(("partial", res.text))
        else:
            res = self.engine.transcribe(self._buf)
            if res.text:
                events.append(("partial", res.text))

    def _drain_ready(self, events: list) -> None:
        """Deliver transcriptions completed since the last feed (async
        planes only; the inline base has none)."""

    def _utterance_closed(self) -> None:
        """Per-utterance server-side state can be released (async planes
        rotate their utterance key here)."""

    def feed(self, samples: np.ndarray) -> list[tuple[str, str]]:
        t_feed0 = time.perf_counter()
        sr = self.engine.mel_cfg.sample_rate
        events: list[tuple[str, str]] = []
        self._drain_ready(events)
        ended = self.endpointer.feed(samples)
        self._buf = np.concatenate([self._buf, samples.astype(np.float32)])
        self._since_partial += len(samples) / sr

        # bound the buffer: outside speech only the top transcription window
        # matters, so an open mic on silence cannot grow memory (and each
        # append stays O(window), not O(session)). The trim invalidates
        # incremental frame accounting, so that state resets with it
        # (outside speech it holds nothing worth keeping).
        max_samples = self.engine.frame_buckets[-1] * self.engine.mel_cfg.hop
        if not self.endpointer.in_speech and len(self._buf) > max_samples:
            self._buf = self._buf[-max_samples:]
            self._inc = None

        # speculative final: once the speaker pauses, the utterance's audio
        # content is frozen — only the endpoint CONFIRMATION is pending. The
        # exact full-window transcription runs now, hidden inside the
        # trailing-silence window, so confirmation only delivers it (cuts
        # the final's transcribe cost out of the end-of-speech->final path).
        # Staleness keys on the endpointer's monotone speech-frame counter:
        # any speech after the speculation (even one 20 ms frame a chunk
        # boundary would hide) makes it unusable.
        spoken = self.endpointer.total_speech_frames
        if (not ended and self.endpointer.in_trailing_silence
                and self._spec_at_speech != spoken):
            # surface the speculation so the PARSE can also start inside the
            # endpoint window (VERDICT round-3 next #3: the transcription
            # was speculated but the parse still waited out the window).
            # Consumers treat it as a hint: a "final" with the same text
            # confirms it; any other final supersedes it.
            self._start_speculation(spoken, events)

        # adaptive early endpoint: every condition is re-validated HERE, on
        # the feed thread, against current endpointer state — the async
        # parse_complete notification alone can never close anything
        fresh = self._spec_final is not None and self._spec_at_speech == spoken
        if (not ended and fresh and self._spec_final.text
                and self._parse_done == self._spec_final.text
                and self.early_close_ms is not None
                and self.endpointer.silence_run_ms >= self.early_close_ms
                and self.endpointer.force_end()):
            ended = True
            self.early_closes += 1
            _metrics().inc("stt.endpoint_early_close")
        elif ended:
            self.window_closes += 1
            _metrics().inc("stt.endpoint_window_close")

        if ended:
            # final: exact full-window transcription (speculated above when
            # the pause was long enough to have been seen). None = the
            # batched plane deferred delivery to its future.
            res = self._final_result(fresh, spoken)
            if res is not None:
                self.last_final = res
            if res is not None and res.text:
                events.append(("final", res.text))
            self._buf = np.zeros(0, dtype=np.float32)
            self._since_partial = 0.0
            self._inc = None
            self._spec_final = None
            self._spec_at_speech = -1
            self._parse_done = None
            self._utterance_closed()
        elif (self.endpointer.in_speech and not self.endpointer.in_trailing_silence
              and self._since_partial >= self.partial_interval_s):
            # no partials once the speaker pauses: the content is frozen and
            # the speculative final above already covers it
            self._since_partial = 0.0
            self._emit_partial(events)

        # saturation gauges: audio-seconds buffered vs processed. The lag
        # accumulates each feed's wall-time excess over the audio duration
        # it consumed and drains when processing runs ahead of realtime;
        # the exported gauges aggregate across ALL live streams (max lag,
        # summed buffered seconds) instead of last-writer-wins.
        self._feed_lag_s = max(
            0.0, self._feed_lag_s + (time.perf_counter() - t_feed0) - len(samples) / sr)
        _record_stream_gauges(self, self._feed_lag_s, len(self._buf) / sr)
        return events


class NullSTT:
    """Offline stand-in (reference analog: the null-Deepgram-key passthrough,
    apps/voice/src/server.ts:68-72). Scripted transcripts for tests."""

    def __init__(self, scripted: list[tuple[str, str]] | None = None):
        self.scripted = list(scripted or [])
        self.fed_samples = 0
        self.fail_next = False  # fault injection (SURVEY.md §5 rebuild note)

    def reset(self) -> None:
        self.fed_samples = 0

    def feed(self, samples: np.ndarray) -> list[tuple[str, str]]:
        if self.fail_next:
            self.fail_next = False
            raise RuntimeError("injected STT fault")
        self.fed_samples += len(samples)
        if self.scripted:
            return [self.scripted.pop(0)]
        return []
