"""``LMEngine``: batched prefill + greedy/temperature decode for the LM archs
(jitted prefill and decode steps, KV/state cache carried on device).

Kept apart from the tree engine so that a tree server never imports the LM
model."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm


class LMEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_seq: int = 1024):
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self._prefill = jax.jit(lambda p, b: tfm.prefill(cfg, p, b, max_seq=max_seq))
        self._decode = jax.jit(lambda p, c, t: tfm.decode_step(cfg, p, c, t))

    def generate(self, batch: dict, n_tokens: int, *, temperature: float = 0.0,
                 seed: int = 0):
        """Greedy (T=0) or sampled decode.  Returns (B, n_tokens) int32."""
        logits, cache = self._prefill(self.params, batch)
        key = jax.random.PRNGKey(seed)
        toks = []
        b = logits.shape[0]
        for i in range(n_tokens):
            if temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits / temperature, axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            nxt = nxt.astype(jnp.int32).reshape(b, 1)
            toks.append(nxt)
            logits, cache = self._decode(self.params, cache, nxt)
        return jnp.concatenate(toks, axis=1)
