"""Batched serving engine restoring weights through an XTable-translated view.

Scenario 3 transplanted: the trainer commits checkpoints in one format's
metadata; the *server* opens the same directory through ANY translated
view (e.g. Iceberg, whose snapshot+manifest metadata with file statistics
is the right shape for a serving fleet's scan planning).  No weight files
are copied.  :meth:`ServeEngine.from_lake` can restore three ways: from a
raw base path, through the read plane's pinned snapshots
(``read_plane=``), or by catalog NAME (``catalog=`` + ``table=``) — the
latter pins the restore at the catalog's published (token, commit), not
whatever head a concurrent sync may have half-landed.

The engine itself: synchronous batched decode with greedy/temperature
sampling over prefill + step functions built from the model zoo.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import LSTCheckpointManager
from repro.models.model import Model
from repro.models.param import template_shapes


@dataclass
class Request:
    prompt: list            # token ids
    max_new: int = 16


class ServeEngine:
    def __init__(self, model: Model, params, *, cache_len: int = 256):
        self.model = model
        self.params = params
        self.cache_len = cache_len
        self._prefill = jax.jit(
            lambda p, t, e=None: model.prefill(
                p, t, cache_len=cache_len,
                **({"enc_embeds": e} if model.cfg.encoder else {})))
        self._step = jax.jit(model.decode_step)

    @classmethod
    def from_lake(cls, model: Model, fs, ckpt_path: str | None = None, *,
                  fmt: str = "iceberg", cache_len: int = 256,
                  read_plane=None, catalog=None,
                  table: str | None = None) -> "ServeEngine":
        """Restore weights through the translated ``fmt`` view.

        With a ``read_plane`` (:class:`~repro.serve.read_plane
        .SnapshotServer`) the checkpoint table resolves through a
        memoized head-keyed snapshot instead of a private metadata
        replay — a fleet of servers restoring the same checkpoint shares
        ONE replay (single-flight) and each later restore's metadata
        cost is a cache hit.

        With a ``catalog`` (:class:`~repro.lst.catalog.Catalog`) the
        table is addressed by registered ``table`` *name* instead of a
        storage path: the catalog pointer supplies the base path and the
        published ``(token, commit)`` pin for the requested view, so the
        restore observes exactly the atomically published head — not
        whatever a concurrent sync has half-landed since.  (The pin
        itself rides the read plane; a catalog without a ``read_plane``
        still resolves the path by name but restores the live head.)
        """
        table_state = None
        if catalog is not None:
            if table is None:
                raise ValueError("catalog-based restore needs table=<name>")
            ptr = catalog.resolve(table)
            ckpt_path = ptr.base_path
            ref = ptr.view(fmt)
            if read_plane is not None:
                table_state = read_plane.read_at(ckpt_path, fmt,
                                                 ref.token, ref.commit).state
        elif ckpt_path is None:
            raise ValueError("need ckpt_path (or catalog= + table=)")
        elif read_plane is not None:
            table_state = read_plane.read(ckpt_path, fmt).snapshot.state
        mgr = LSTCheckpointManager(fs, ckpt_path, fmt=fmt, sync_targets=())
        shapes = template_shapes(model.param_template())
        _, state = mgr.restore_pytree({"params": shapes}, fmt=fmt,
                                      state=table_state)
        return cls(model, jax.tree.map(jnp.asarray, state["params"]),
                   cache_len=cache_len)

    def generate(self, requests: list, *, temperature: float = 0.0,
                 seed: int = 0, return_logits: bool = False):
        """Synchronous batched generation (greedy when temperature == 0).

        Prompts are left-padded with token ``vocab_size - 1`` to the longest
        one. With ``return_logits`` the result is ``(outs, logits)``, where
        ``logits[t]`` is the (batch, vocab) float32 array token ``t`` was
        sampled from.
        """
        b = len(requests)
        max_prompt = max(len(r.prompt) for r in requests)
        max_new = max(r.max_new for r in requests)
        pad = self.model.cfg.vocab_size - 1
        toks = np.full((b, max_prompt), pad, np.int32)
        for i, r in enumerate(requests):
            toks[i, -len(r.prompt):] = r.prompt      # left-pad
        enc = None
        if self.model.cfg.encoder:
            enc = jnp.zeros((b, self.model.cfg.encoder.n_frames,
                             self.model.cfg.d_model), self.model.cfg.dtype)
        args = (self.params, jnp.asarray(toks)) + \
            ((enc,) if enc is not None else ())
        logits, cache = self._prefill(*args)
        key = jax.random.PRNGKey(seed)
        outs = [[] for _ in range(b)]
        pos = jnp.full((b,), max_prompt, jnp.int32)
        tok = self._sample(logits, temperature, key)
        seen = []
        for step in range(max_new):
            if return_logits:
                seen.append(logits)
            for i in range(b):
                if step < requests[i].max_new:
                    outs[i].append(int(tok[i]))
            if step + 1 >= max_new:
                # every request has its tokens; the trailing decode step
                # would be sampled and thrown away
                break
            key, sub = jax.random.split(key)
            logits, cache = self._step(self.params, cache, tok, pos)
            tok = self._sample(logits, temperature, sub)
            pos = pos + 1
        if return_logits:
            return outs, np.asarray(jnp.stack(seen), np.float32)
        return outs

    @staticmethod
    def _sample(logits, temperature: float, key):
        if temperature == 0.0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, -1) \
            .astype(jnp.int32)
