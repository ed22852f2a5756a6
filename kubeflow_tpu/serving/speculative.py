"""Draft-token proposers for speculative decoding.

The continuous decoder's verify path (models/decode.py:verify_step /
verify_chunk) multiplies decode throughput by scoring K cheap draft
tokens per dispatch — THIS module is where the cheap drafts come from.
Two proposers, both pluggable behind the same ``propose`` surface:

- :class:`NgramProposer` — "prompt lookup" drafting: the continuation
  that followed the most recent earlier occurrence of the context's
  trailing n-gram. Pure host logic, zero device memory, zero model
  cost — the right default for summarization/extraction/code traffic
  where outputs quote their inputs, and for any model that has settled
  into a repeating pattern.
- :class:`DraftModelProposer` — a small registry model
  (``draft_mode="model:<name>"``) holding its OWN decode state over the
  same slot layout as the target. Each round is ONE fused dispatch
  (models/decode.py:extend_and_propose): force-feed the tokens the
  target committed since last round (which silently overwrites anything
  the target rejected — the feed position IS the rollback), then decode
  the next proposals greedily.

Proposals are hints, never promises: verification accepts only what the
target itself would have produced, so a wrong draft costs compute, not
correctness.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.decode import extend_and_propose, init_decode_state
from kubeflow_tpu.models.registry import get_model
from kubeflow_tpu.models.transformer import serving_params
from kubeflow_tpu.serving.engine import pow2_bucket


class NgramProposer:
    """Host-side prompt/output n-gram lookup.

    ``propose`` scans the context for the most recent earlier occurrence
    of its trailing ``m``-gram (longest match first, ``max_match`` down
    to ``min_match``) and proposes the tokens that followed it. O(len *
    max_match) per call over serving-sized contexts.
    """

    def __init__(self, max_match: int = 3, min_match: int = 1):
        self.max_match = max(1, int(max_match))
        self.min_match = max(1, min(int(min_match), self.max_match))
        self.dispatches = 0  # ngram drafting never touches the device

    def reset(self, slot: int) -> None:  # per-slot state: none
        pass

    def install_weights(self, params) -> None:
        """N-gram drafting has no weights — a live weight push is a
        no-op here (the proposer reads committed tokens, which are
        already the new model's outputs after the swap)."""

    def _lookup(self, context: list[int], n: int) -> list[int]:
        if n <= 0:
            return []
        for m in range(self.max_match, self.min_match - 1, -1):
            if len(context) <= m:
                continue
            pat = context[-m:]
            # Rightmost occurrence strictly before the trailing one —
            # recent repetition predicts the continuation best.
            for i in range(len(context) - m - 1, -1, -1):
                if context[i:i + m] == pat:
                    seg = context[i + m: i + m + n]
                    if seg:
                        return seg
                    break  # the match butts against the tail: shorter m
        return []

    def propose(self, requests: list[tuple[int, list[int], int]],
                ) -> dict[int, list[int]]:
        """``requests``: (slot, context tokens, max proposal length) per
        live row → slot -> proposed tokens (possibly empty)."""
        return {slot: self._lookup(ctx, n) for slot, ctx, n in requests}


class DraftModelProposer:
    """Small draft model sharing the target's slot layout.

    Keeps a private decode state (``slots`` rows, the target's
    ``total_len``) for the draft model and tracks, per slot, how many of
    the request's committed tokens its cache already holds. A propose
    round is one dispatch: catch-up feed + ``propose_steps`` greedy
    tokens per row.
    """

    def __init__(self, model_name: str, target_vocab: int, slots: int,
                 total_len: int, propose_steps: int, seed: int = 0):
        spec = get_model(model_name)
        if spec.family != "transformer":
            raise ValueError(
                f"draft model {model_name!r} is {spec.family}, need a "
                "transformer"
            )
        if spec.config.vocab_size != target_vocab:
            raise ValueError(
                f"draft model {model_name!r} vocab "
                f"{spec.config.vocab_size} != target vocab {target_vocab}"
            )
        self.cfg = spec.config
        self.params = serving_params(
            spec.init(jax.random.PRNGKey(seed), self.cfg), self.cfg)
        self.slots = slots
        self.total_len = total_len
        self.propose_steps = max(1, int(propose_steps))
        self.state = init_decode_state(self.cfg, slots, total_len, seed)
        self._fed = [0] * slots  # context tokens already in the draft cache
        self.dispatches = 0

    def reset(self, slot: int) -> None:
        """A new request took ``slot``: its whole prompt is pending feed
        (the stale cache content is overwritten as the feed advances)."""
        self._fed[slot] = 0

    def install_weights(self, params) -> None:
        """Swap in new draft weights (already device-placed by the
        caller). The decoder installs this INSIDE the same state-lock
        epoch as the target's swap — a draft proposing from old weights
        against a new-weights verifier doesn't break correctness
        (verification accepts only what the target would emit) but
        silently collapses acceptance, which is the entire throughput
        win. The draft KV cache is NOT invalidated: positions fed
        before the swap were committed target tokens either way, and
        the proposer's output is a hint the verify pass re-scores."""
        self.params = params

    def propose(self, requests: list[tuple[int, list[int], int]],
                ) -> dict[int, list[int]]:
        if not requests:
            return {}
        pend = {slot: max(len(ctx) - self._fed[slot], 0)
                for slot, ctx, _n in requests}
        width = pow2_bucket(max(max(pend.values()), 1), cap=self.total_len)
        feed = np.zeros((self.slots, width), np.int32)
        # Unused rows park at the cache end: their writes drop on device.
        pos = np.full((self.slots,), self.total_len, np.int32)
        lens = np.zeros((self.slots,), np.int32)
        for slot, ctx, _n in requests:
            p = min(pend[slot], width)
            seg = ctx[self._fed[slot]: self._fed[slot] + p]
            feed[slot, : len(seg)] = seg
            pos[slot] = self._fed[slot]
            lens[slot] = len(seg)
            self._fed[slot] += len(seg)
        self.state, props = extend_and_propose(
            self.state, self.params, self.cfg, jnp.asarray(feed),
            jnp.asarray(pos), jnp.asarray(lens), self.propose_steps)
        self.dispatches += 1
        props = np.asarray(props)
        return {slot: props[slot, :n].tolist() for slot, ctx, n in requests}


def make_proposer(draft_mode: str, *, target_vocab: int, slots: int,
                  total_len: int, propose_steps: int, seed: int = 0):
    """``draft_mode`` → proposer: ``"ngram"`` or ``"model:<registry-name>"``
    (the ``--draft-mode`` flag surface)."""
    if draft_mode == "ngram":
        return NgramProposer()
    if draft_mode.startswith("model:"):
        return DraftModelProposer(
            draft_mode[len("model:"):], target_vocab, slots, total_len,
            propose_steps, seed=seed)
    raise ValueError(
        f"unknown draft_mode {draft_mode!r}; expected 'ngram' or "
        "'model:<registry-name>'"
    )
