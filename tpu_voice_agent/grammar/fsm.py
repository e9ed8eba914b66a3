"""Token-level FSM: lift a byte DFA to (state, token) transitions, compressed.

The device-side artifact of grammar-constrained decoding. Round 1 stored the
transition relation dense as ``(S, V)`` int32 + bool tables; at a real
checkpoint vocab (V = 32k for TinyLlama, 128k for Llama-3) and S ≈ 6k DFA
states that is gigabytes of HBM — a design wall. The fix is **token-class column compression**: two
tokens are equivalent iff their next-state columns agree across all states,
and in practice almost every token in a large vocab is either dead everywhere
or behaves like one of a few hundred representatives (the intent grammar has
~300 distinct columns at any vocab size). So we store

  - ``col_id``  (V,) int32 — token → equivalence class
  - ``table``   (S, C) int32 — next state per (state, class); -1 = dead

and recover a full vocab row on device with two gathers:
``row = table[state][col_id]`` (one (C,) gather + one (V,) take that XLA
fuses into the logit-mask loop). Memory is S·C + V instead of S·V — the
intent grammar at Llama-3 scale drops from ~3 GB to ~8 MB.

At each decode step the engine masks logits where ``row < 0`` and advances
per-sequence state with ``table[state, col_id[tok]]`` — no host round-trip
per token (SURVEY.md §7 hard part #1).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .regexlang import DFA


class DeviceFSM(NamedTuple):
    """Device-resident FSM tables (a jit-traceable pytree).

    ``dense_mask`` is populated only for small vocabs (the Pallas
    ``masked_argmax`` kernel streams dense (S, V) mask tiles); ``None``
    switches the engine to the compressed XLA path.

    ``ff_tokens``/``ff_len`` (grammar fast-forward, optional): for each
    state, the canonical tokenization of its FORCED byte run — the unique
    byte path the grammar admits (JSON scaffolding between free choices).
    The decode loop appends these without sampling: in the memory-bound
    decode regime a (1+W)-token forward costs the same HBM traffic as a
    1-token forward, so forced tokens are nearly free.
    """

    table: jax.Array  # (S, C) int32; -1 = dead
    col_id: jax.Array  # (V,) int32 token -> class
    dense_mask: Optional[jax.Array]  # (S, V) bool or None
    ff_tokens: Optional[jax.Array] = None  # (S, W) int32; -1 pad
    ff_len: Optional[jax.Array] = None  # (S,) int32 0..W


def fsm_row(t: DeviceFSM, state: jax.Array) -> jax.Array:
    """(B,) states -> (B, V) int32 next-state row (-1 = disallowed)."""
    return jnp.take(t.table[state], t.col_id, axis=-1)


def fsm_advance(t: DeviceFSM, state: jax.Array, tok: jax.Array) -> jax.Array:
    """(B,) states, (B,) sampled tokens -> (B,) next states."""
    return t.table[state, t.col_id[tok]]


class TokenFSM:
    """Column-compressed (state, token) transition relation.

    Built by a vectorized DFS over the vocab byte trie: each trie node
    carries the (S,) vector of DFA states reached from every start state by
    the node's byte prefix; a token's column is the vector at its leaf,
    interned into the class table by content hash. Tokens never reached
    (dead from every state) share class 0, the all-dead column.

    ``vocab_size`` may exceed the tokenizer's (checkpoints pad their embed
    table); the extra ids are dead.
    """

    def __init__(self, dfa: DFA, tokenizer, vocab_size: int | None = None):
        S = dfa.num_states
        V = int(vocab_size or tokenizer.vocab_size)
        if V < tokenizer.vocab_size:
            raise ValueError(
                f"vocab_size {V} smaller than tokenizer vocab {tokenizer.vocab_size}"
            )
        trans_b = dfa.trans[:, dfa.class_of]  # (S, 256) byte-expanded
        identity = np.arange(S, dtype=np.int32)

        # trie over token byte pieces; distinct ids may share bytes (real
        # vocabs carry duplicates via added_tokens), so leaves hold id lists
        trie: dict = {}
        for tid, piece in enumerate(tokenizer.byte_pieces()):
            if not piece:  # None or b"": specials / non-emitting tokens
                continue
            node = trie
            for b in piece:
                node = node.setdefault(b, {})
            node.setdefault(-1, []).append(tid)

        dead = np.full((S,), -1, dtype=np.int32)
        columns: list[np.ndarray] = [dead]
        col_of: dict[bytes, int] = {dead.tobytes(): 0}
        col_id = np.zeros((V,), dtype=np.int32)

        def intern(vec: np.ndarray) -> int:
            key = vec.tobytes()
            idx = col_of.get(key)
            if idx is None:
                idx = len(columns)
                col_of[key] = idx
                columns.append(vec)
            return idx

        stack: list[tuple[dict, np.ndarray]] = [(trie, identity)]
        while stack:
            node, vec = stack.pop()
            alive = vec >= 0
            for key, child in node.items():
                if key == -1:
                    c = intern(vec)
                    for tid in child:
                        col_id[tid] = c
                else:
                    nvec = np.where(alive, trans_b[np.maximum(vec, 0), key], -1).astype(
                        np.int32
                    )
                    if (nvec >= 0).any():
                        stack.append((child, nvec))

        # EOS is allowed exactly on accepting states and keeps the state
        # (finished rows are excluded from further stepping by the engine).
        # (pad/bos need no forcing: true specials carry piece=None and are
        # dead already, while a checkpoint whose pad falls back to a content
        # token keeps that token usable inside JSON strings)
        eos_vec = np.where(dfa.accepting, identity, -1).astype(np.int32)
        col_id[tokenizer.eos_id] = intern(eos_vec)

        self.table = np.stack(columns, axis=1)  # (S, C)
        self.col_id = col_id
        self.start = dfa.start
        self.num_states = S
        self.num_classes = len(columns)
        self.vocab_size = V
        self.accepting = dfa.accepting.copy()
        # kept for forced_tables(): byte-expanded transitions + piece trie
        self._trans_b = trans_b
        self._trie = trie
        self._forced_arr: tuple[np.ndarray, np.ndarray] | None = None

    # ------------------------------------------------------------ dense views

    @property
    def next_state(self) -> np.ndarray:
        """Dense (S, V) int32 view — O(S·V); tests and toy vocabs only."""
        return self.table[:, self.col_id]

    @property
    def mask(self) -> np.ndarray:
        """Dense (S, V) bool view — O(S·V); tests and toy vocabs only."""
        return self.next_state >= 0

    # ------------------------------------------------------------ host stepping

    def allowed(self, state: int) -> np.ndarray:
        return self.table[state][self.col_id] >= 0

    def step(self, state: int, token_id: int) -> int:
        return int(self.table[state, self.col_id[token_id]])

    def walk(self, token_ids: list[int]) -> int:
        s = self.start
        for t in token_ids:
            s = self.step(s, t)
            if s < 0:
                return s
        return s

    # ------------------------------------------------------------ fast-forward

    def _forced_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """(forced (S,) bool, fbyte (S,) int): a state is "forced" when the
        byte DFA admits exactly one byte and is not accepting (accepting
        adds the EOS choice); fbyte is that byte. Computed once."""
        if self._forced_arr is None:
            legal = self._trans_b >= 0  # (S, 256)
            forced = (legal.sum(axis=1) == 1) & ~self.accepting
            self._forced_arr = (forced, np.argmax(legal, axis=1))
        return self._forced_arr

    def _forced_run(self, state: int) -> list[int]:
        """The unique forced byte path from ``state`` ([] when the state is
        a free choice point / dead / accepting). Any grammar-legal
        continuation must emit these bytes."""
        forced, fbyte = self._forced_arrays()
        run, st = [], state
        while forced[st] and len(run) < 4096:
            b = int(fbyte[st])
            run.append(b)
            st = int(self._trans_b[st, b])
        return run

    def _tile_run(self, run: list[int], width: int) -> list[int]:
        """Greedy-longest canonical tokenization of a byte run over the
        vocab trie (first id of a piece = canonical): ``forced_tables``'
        canonical-tiling convention."""
        toks, i = [], 0
        while i < len(run) and len(toks) < width:
            node, best, j = self._trie, None, i
            while j < len(run) and run[j] in node:
                node = node[run[j]]
                j += 1
                if -1 in node:
                    best = (j, node[-1][0])
            if best is None:
                break  # no piece tiles here; stop fast-forwarding
            i = best[0]
            toks.append(best[1])
        return toks

    def forced_tables(self, width: int) -> tuple[np.ndarray, np.ndarray]:
        """(ff_tokens (S, width) int32, ff_len (S,) int32): per state, the
        canonical tokenization (``_tile_run``) of its forced byte run
        (``_forced_run``). Runs longer than ``width`` tokens continue next
        step because the state after a truncated chain is itself forced.
        Chains never contain EOS (runs stop before accepting states).
        """
        S = self.num_states
        forced, _ = self._forced_arrays()
        ff_tokens = np.full((S, width), -1, dtype=np.int32)
        ff_len = np.zeros((S,), dtype=np.int32)
        for s in range(S):
            if not forced[s]:
                continue
            toks = self._tile_run(self._forced_run(s), width)
            ff_tokens[s, : len(toks)] = toks
            ff_len[s] = len(toks)
        return ff_tokens, ff_len

    # ------------------------------------------------------------ device tables

    def device_tables(self, dense_limit: int = 1 << 25, ff_width: int = 0) -> DeviceFSM:
        """Ship tables to device. The dense bool mask (Pallas masked_argmax
        fodder) is included only while S·V stays under ``dense_limit``
        entries (32M default = 32 MB of bool); past that the engine's
        compressed XLA path is the only sane layout. ``ff_width > 0``
        attaches the grammar fast-forward chains (forced_tables)."""
        dense = None
        if self.num_states * self.vocab_size <= dense_limit:
            dense = jnp.asarray(self.mask)
        ff_tok = ff_len = None
        if ff_width > 0:
            t, l = self.forced_tables(ff_width)
            ff_tok, ff_len = jnp.asarray(t), jnp.asarray(l)
        return DeviceFSM(
            table=jnp.asarray(self.table),
            col_id=jnp.asarray(self.col_id),
            dense_mask=dense,
            ff_tokens=ff_tok,
            ff_len=ff_len,
        )


def sample_dfa(dfa: DFA, rng: np.random.Generator, max_len: int = 4000) -> bytes:
    """Random-walk the DFA to an accepting state (test/debug helper)."""
    # representative bytes per class
    by_class: dict[int, list[int]] = {}
    for b in range(256):
        by_class.setdefault(int(dfa.class_of[b]), []).append(b)
    out = bytearray()
    s = dfa.start
    for _ in range(max_len):
        if dfa.accepting[s] and rng.random() < 0.3:
            return bytes(out)
        classes = np.nonzero(dfa.trans[s] >= 0)[0]
        if len(classes) == 0:
            if dfa.accepting[s]:
                return bytes(out)
            raise RuntimeError("stuck in non-accepting state with no moves")
        c = int(rng.choice(classes))
        b = int(rng.choice(by_class[c]))
        out.append(b)
        s = int(dfa.trans[s, c])
    # budget exhausted: walk greedily toward accept by preferring structural bytes
    for _ in range(2000):
        if dfa.accepting[s]:
            return bytes(out)
        classes = np.nonzero(dfa.trans[s] >= 0)[0]
        # prefer classes containing closing punctuation to terminate quickly
        pick = None
        for c in classes:
            if any(ch in by_class[int(c)] for ch in (0x22, 0x5D, 0x7D, 0x2C, 0x3A)):
                pick = int(c)
                break
        c = pick if pick is not None else int(classes[0])
        b = by_class[c][0]
        out.append(b)
        s = int(dfa.trans[s, c])
    raise RuntimeError("could not reach accepting state")
