"""Sequence episodes: a member's episode is one teacher-forced forward over a
token sequence, its fitness the mean log-likelihood of the next token.

ES needs forward passes only, which is why it is used to fine-tune language
models (PAPERS.md, arXiv 2511.16652 and 2509.24372).  There is no step to
scan: the env hands the policy a whole sequence and scores what comes back
in one call, which ``envs/rollout.py::make_rollout`` recognises by
``whole_episode``.  The policy (models/hybrid_lm.py) returns ``(log p of
each next token [T-1], the last position's logits [vocab])``; what a model
returns after those two (models/moe_lm.py: its experts' load) the rollout
passes on as ``RolloutResult.extras``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TokenScoreEnv:
    """A seeded corpus of ``corpus_sequences`` sequences of ``seq_len``
    uniform random token ids, held on the device.  ``reset(key)`` picks one:
    an antithetic pair shares its rollout key, so both signs see the same
    tokens (common random numbers, as everywhere in the engines).

    ``horizon`` (tokens per member) is ``seq_len``; an episode's ``steps``
    are the tokens passed through the model.  The behaviour vector is the
    last position's logits at ``bc_dim`` fixed vocabulary ids (spread over
    the vocabulary), so that whoever compares behaviours compares logits.
    """

    vocab_size: int
    seq_len: int
    corpus_sequences: int = 256
    seed: int = 0
    bc_dim: int = 32

    whole_episode = True
    discrete = True

    @property
    def obs_dim(self) -> int:
        return self.seq_len

    @property
    def action_dim(self) -> int:
        return self.vocab_size

    @property
    def default_horizon(self) -> int:
        return self.seq_len

    def corpus(self) -> jax.Array:
        """``[corpus_sequences, seq_len]`` int32; a constant of the
        compiled program (a megabyte per 256 sequences of 1024)."""
        return jax.random.randint(
            jax.random.PRNGKey(self.seed),
            (self.corpus_sequences, self.seq_len), 0, self.vocab_size,
            dtype=jnp.int32)

    def probe_ids(self) -> jax.Array:
        return (jnp.arange(self.bc_dim, dtype=jnp.int32)
                * (self.vocab_size // self.bc_dim))

    def reset(self, key: jax.Array):
        row = jax.random.randint(key, (), 0, self.corpus_sequences)
        tokens = jax.lax.dynamic_index_in_dim(
            self.corpus(), row, axis=0, keepdims=False)
        return row, tokens

    def step(self, state, action):
        raise NotImplementedError(
            "a whole-episode env has no step: make_rollout calls score")

    def score(self, state, tokens, policy_out):
        """``(fitness, behaviour, steps)`` of one whole episode."""
        del state, tokens
        next_logp, last_logits = policy_out[:2]
        return (jnp.mean(next_logp.astype(jnp.float32)),
                jnp.take(last_logits, self.probe_ids()).astype(jnp.float32),
                jnp.int32(self.seq_len))
