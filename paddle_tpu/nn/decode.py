"""Seq2seq decoding (reference python/paddle/nn/decode.py:
BeamSearchDecoder + dynamic_decode over an RNN cell).

TPU note: the step loop runs in python (host-driven decode, like the
reference's dynamic_decode); each step's compute is dispatched ops, and the
final sequence reconstruction is the registered ``gather_tree`` op.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, to_tensor
from ..ops.registry import OPS

__all__ = ["BeamSearchDecoder", "dynamic_decode", "sample_logits"]


def sample_logits(logits, temperature=1.0, top_k=0, top_p=1.0, key=None):
    """Sample next-token ids from logits — the serving engine's sampler.

    logits:      [V] or [B, V] raw (unnormalized) logits; jit-safe.
    temperature: scalar or [B]. ``0`` means greedy (argmax of the raw
                 logits); rows mix freely (per-row temperatures).
    top_k:       scalar or [B] int; keep only the k largest logits
                 (``0`` disables). Traced values are fine (clamped to
                 [1, V] inside).
    top_p:       scalar or [B]; nucleus sampling — keep the smallest
                 prefix of the sorted distribution with mass >= p
                 (``1.0`` disables; the top-1 token is always kept).
    key:         a PRNG key, or [B] stacked keys for per-row streams
                 (continuous batching needs per-request keys so a row's
                 tokens don't depend on its batch neighbours). May be
                 omitted only for pure-greedy calls.

    Returns int32 ids, scalar for 1-D input. Same key -> same tokens.

    With a key, what only a sampling row needs (both sorts of ``[B, V]``,
    the softmax, the cumulative sum, the ``[B, V]`` uniform draw) is the
    taken branch of one ``jax.lax.cond`` on ``any(temperature > 0)``, read
    on the device in the same compiled program: an all-greedy batch runs
    the argmax and nothing else; one sampling row pays for all ``B`` rows,
    as every batch did before. Under ``jax.vmap`` a ``cond`` becomes a
    select that runs both branches: batch through ``[B, V]`` logits.
    """
    squeeze = logits.ndim == 1
    # the cast reads the values the model gave in its own dtype: without the
    # barrier XLA may fold it into the head's matmul and hand on the float32
    # accumulator's bits, so bf16 logits that tie would no longer tie and
    # the served token would depend on what the compiler fused
    lg = jax.lax.optimization_barrier(
        logits[None] if squeeze else logits).astype(jnp.float32)
    B, V = lg.shape
    temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    tk = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    tp = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

    if key is None:
        tok = greedy  # greedy-only call; sampling rows need a key
    else:
        key = jnp.asarray(key)

        def sample_rows():
            if key.ndim == 2:
                keys = key
            elif B == 1:
                # a lone row consumes the key directly, so batched callers
                # that fold a per-request key per row (the engine) and
                # single-row callers (prefill / naive_generate) draw the
                # SAME stream
                keys = key[None]
            else:
                keys = jax.random.split(key, B)
            desc = jnp.sort(lg, axis=-1)[:, ::-1]
            # top-k: threshold at the k-th largest logit (k=0 -> keep all)
            k_eff = jnp.clip(jnp.where(tk <= 0, V, tk), 1, V)
            kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
            masked = jnp.where(lg >= kth, lg, -jnp.inf)
            # top-p over the surviving distribution: keep sorted entries
            # whose *exclusive* cumulative mass is < p (always keeps the
            # top-1)
            probs = jax.nn.softmax(masked, axis=-1)
            sp = jnp.sort(probs, axis=-1)[:, ::-1]
            csum = jnp.cumsum(sp, axis=-1)
            first = jnp.arange(V, dtype=jnp.int32)[None] == 0
            keep = ((csum - sp) < tp[:, None]) | first
            thresh = jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1,
                             keepdims=True)
            masked = jnp.where(probs >= thresh, masked, -jnp.inf)
            # Gumbel-max with a per-row key: argmax(logits/T + g)
            scaled = masked / jnp.maximum(temp, 1e-6)[:, None]
            u = jax.vmap(lambda kk: jax.random.uniform(
                kk, (V,), minval=1e-20, maxval=1.0))(keys)
            return jnp.argmax(scaled - jnp.log(-jnp.log(u)),
                              axis=-1).astype(jnp.int32)

        sampled = jax.lax.cond(jnp.any(temp > 0), sample_rows,
                               lambda: greedy)
        tok = jnp.where(temp > 0, sampled, greedy)
    return tok[0] if squeeze else tok


def _np(x):
    return np.asarray(x.numpy() if isinstance(x, Tensor) else x)


class BeamSearchDecoder:
    """Beam search over a cell: state carries (cell states per beam,
    cumulative log-probs, finished flags)."""

    def __init__(self, cell, start_token, end_token, beam_size,
                 embedding_fn=None, output_fn=None):
        self.cell = cell
        self.start_token = int(start_token)
        self.end_token = int(end_token)
        self.beam_size = int(beam_size)
        self.embedding_fn = embedding_fn
        self.output_fn = output_fn

    # -- reference API ----------------------------------------------------
    def initialize(self, initial_cell_states):
        """Tile cell states across beams; beam 0 starts live, others -inf."""
        k = self.beam_size

        def tile(s):
            a = _np(s)
            return np.repeat(a, k, axis=0)  # [b*k, ...] beam-major per batch

        states = jax.tree_util.tree_map(tile, initial_cell_states)
        batch = jax.tree_util.tree_leaves(initial_cell_states)[0].shape[0]
        log_probs = np.full((batch, k), -1e9, np.float32)
        log_probs[:, 0] = 0.0
        finished = np.zeros((batch, k), bool)
        tokens = np.full((batch, k), self.start_token, np.int64)
        return tokens, (states, log_probs, finished)

    def step(self, time, tokens, beam_state):
        states, log_probs, finished = beam_state
        batch, k = tokens.shape
        inp = to_tensor(tokens.reshape(-1))
        if self.embedding_fn is not None:
            inp = self.embedding_fn(inp)
        cell_out, new_states = self.cell(
            inp, jax.tree_util.tree_map(to_tensor, states))
        logits = self.output_fn(cell_out) if self.output_fn else cell_out
        logp = _np(logits).astype(np.float32)
        logp = logp - _logsumexp(logp)  # log-softmax, [b*k, V]
        V = logp.shape[-1]
        logp = logp.reshape(batch, k, V)
        # finished beams only extend with end_token at no cost
        fin_mask = np.full((V,), -1e9, np.float32)
        fin_mask[self.end_token] = 0.0
        logp = np.where(finished[:, :, None], fin_mask[None, None], logp)
        total = log_probs[:, :, None] + logp  # [b, k, V]
        flat = total.reshape(batch, k * V)
        top = np.argsort(-flat, axis=1)[:, :k]  # [b, k]
        new_log_probs = np.take_along_axis(flat, top, axis=1)
        parent = (top // V).astype(np.int64)
        token = (top % V).astype(np.int64)
        new_finished = np.take_along_axis(finished, parent, axis=1) | (
            token == self.end_token)

        def regather(s):
            a = _np(s).reshape((batch, k) + _np(s).shape[1:])
            idx = parent
            for _ in range(a.ndim - 2):
                idx = idx[..., None]
            out = np.take_along_axis(a, np.broadcast_to(idx, a.shape), axis=1)
            return out.reshape((batch * k,) + a.shape[2:])

        new_states = jax.tree_util.tree_map(
            regather, jax.tree_util.tree_map(_np, new_states))
        return (token, parent), (new_states, new_log_probs, new_finished)


_ACCEPTED_NOOP_KWARGS = {"impute_finished", "is_test"}


def dynamic_decode(decoder, inits=None, max_step_num=32,
                   output_time_major=False, return_length=False, **kwargs):
    """Run the decoder to completion (reference dynamic_decode).

    Returns (sequences, final log-probs [b, beam]); sequences are
    batch-major [b, T, beam] by default (the reference's
    output_time_major=False), time-major with output_time_major=True.
    With return_length=True a third [b, beam] int array gives each
    sequence's length including its end token. Reconstruction goes through
    the ``gather_tree`` op."""
    for k in kwargs:
        if k not in _ACCEPTED_NOOP_KWARGS:
            raise TypeError(f"dynamic_decode got unexpected argument {k!r}")
    if inits is None:
        raise ValueError(
            "dynamic_decode needs initial cell states (inits=...)")
    if max_step_num < 1:
        raise ValueError("max_step_num must be >= 1")
    tokens, state = decoder.initialize(inits)
    step_tokens, step_parents = [], []
    for t in range(max_step_num):
        (tok, parent), state = decoder.step(t, tokens, state)
        step_tokens.append(tok)
        step_parents.append(parent)
        tokens = tok
        if state[2].all():
            break
    ids = np.stack(step_tokens)      # [T, b, k]
    parents = np.stack(step_parents)
    seqs = OPS["gather_tree"].fn(to_tensor(ids), to_tensor(parents))
    seq_np = _np(seqs)
    if not output_time_major:
        seqs = to_tensor(np.transpose(seq_np, (1, 0, 2)))  # [b, T, k]
    out = (seqs, to_tensor(state[1]))
    if return_length:
        end = getattr(decoder, "end_token", None)
        T = seq_np.shape[0]
        if end is None:
            lengths = np.full(seq_np.shape[1:], T, np.int64)
        else:
            is_end = seq_np == end  # [T, b, k]
            any_end = is_end.any(axis=0)
            first = is_end.argmax(axis=0) + 1
            lengths = np.where(any_end, first, T).astype(np.int64)
        out = out + (to_tensor(lengths),)
    return out


def _logsumexp(a):
    m = a.max(axis=-1, keepdims=True)
    return m + np.log(np.exp(a - m).sum(axis=-1, keepdims=True))

