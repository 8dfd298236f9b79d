"""Speculative decoding: a small drafter proposes, the target verifies.

Leviathan et al.'s speculative sampling adapted to the serving engine's
JAX prefill/decode machinery (ISSUE 12): a cheap DRAFTER model from the
zoo proposes ``gamma`` greedy tokens per round, and the TARGET scores the
whole proposal in ONE batched pass through its existing prefill program
— the whole-sequence forward that tier-1 holds the engine's decode
logits to (within 64 ulp of the largest logit, same greedy token:
tests/serving_oracle.py), so an ACCEPTED token is the token the baseline
greedy decode emits wherever the two argmaxes agree — tier-1 pins the
speculative stream equal to the baseline stream; on the chip two
programs may part at a reference tie (PERF.md §7) — and a rejected
position falls back to the target's own argmax at no extra forward.
Each verification round
therefore commits between 1 (drafter useless) and ``gamma + 1`` (all
accepted + the free bonus token) tokens for one target forward.

Known cost model: drafter proposals re-score the growing stream through
the drafter's bucketed prefill program (no drafter-side KV reuse yet) —
``gamma`` small-model prefills per round next to the one target
verification prefill. For a drafter several times smaller than the
target this still wins on rounds, but a KV-cached one-token drafter
decode (the engine's own decode step pointed at the drafter) is the
obvious next cut and the measured acceptance/round ledger below is what
will price it.

Greedy-only by design: under greedy sampling "distribution-identical"
degenerates to token-identity, which is exactly testable
(tests/test_decode_paged.py pins speculative output == baseline output).
Temperature sampling would need the rejection-sampling correction from
the paper; the decoder refuses it loudly rather than approximating.

Honest accounting: acceptance rates ride ``ServingStats``
(``spec_rounds/spec_proposed/spec_accepted``) and each round's wall and
committed-token count feed the engine's EWMA
:class:`~flexflow_tpu.serving.resilience.AdmissionController` — when
speculation changes the per-token cost, admission shedding sees the REAL
cost, not the non-speculative estimate (the controller additionally
tracks an acceptance EWMA via ``observe_speculation``).
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

import numpy as np

from .engine import ServingStats
from .scheduler import default_buckets


class SpeculativeDecoder:
    """Greedy speculative decoding over two compiled FFModels.

    ``target`` and ``drafter`` must both be autoregressive (single
    integer token input, per-token (batch, seq, vocab) head) and share a
    vocabulary; the drafter is typically a narrower/shallower zoo build.
    ``controller`` (optionally the serving engine's ``admission``) keeps
    the EWMA admission cost model honest under speculation.
    """

    def __init__(self, target, drafter, gamma: int = 4,
                 max_context: Optional[int] = None,
                 controller=None):
        if gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        for which, m in (("target", target), ("drafter", drafter)):
            if m.executor is None:
                raise ValueError(f"{which} model: call compile() first")
        # ISSUE 18 guard rail: greedy speculative verification scores
        # draft windows through the single-shard prefill program; a sequence-
        # sharded target (or drafter) would verify against a different
        # score decomposition than it decodes with. Refuse loudly at
        # construction instead of accepting garbage token streams.
        from .kvcache import SeqShardsError

        for which, m in (("target", target), ("drafter", drafter)):
            if int(getattr(m.config, "seq_shards", 1) or 1) > 1:
                raise SeqShardsError(
                    f"speculative decoding does not support --seq-shards "
                    f"> 1 (the {which} model requests "
                    f"{int(m.config.seq_shards)} sequence shards); run "
                    "the sharded engine without a drafter, or set "
                    "--seq-shards 1")
        t_vocab = self._vocab(target)
        d_vocab = self._vocab(drafter)
        if t_vocab != d_vocab:
            raise ValueError(
                f"target vocab {t_vocab} != drafter vocab {d_vocab}: "
                "speculative verification compares token ids, the two "
                "models must share a vocabulary")
        self.target = target
        self.drafter = drafter
        self.gamma = int(gamma)
        # same bound as the serving engine's admission rejection: the
        # position table caps scorable length on BOTH models (a longer
        # stream would silently alias position rows in the verification
        # forward and break the token-identity contract)
        from .engine import position_context_bound

        requested = int(
            max_context or getattr(target.config, "max_decode_len", 128))
        self.max_context = min(
            position_context_bound(target.executor, requested),
            position_context_bound(drafter.executor, requested))
        self.controller = controller
        self.stats = ServingStats()
        self._buckets = default_buckets(self.max_context)
        # device-side argmax for _score, jitted lazily (retraces per
        # logits bucket shape; one executable per bucket)
        self._argmax = None

    @staticmethod
    def _vocab(model) -> int:
        ex = model.executor
        final = ex.pcg.nodes[ex.final_guid]
        out = final.out_shapes[ex.final_out_idx]
        if len(out) != 3:
            raise ValueError(
                f"speculative decoding needs a per-token (batch, seq, "
                f"vocab) head; {final.name} produces {out}")
        return int(out[-1])

    # ------------------------------------------------------------- scoring
    def _score(self, model, tokens: np.ndarray) -> np.ndarray:
        """Greedy next-token ids for every position of ``tokens`` via the
        model's prefill program (ONE whole-sequence forward — the
        reference the engine's decode logits are held to in tier-1).
        Returns (len,) int32: entry i is argmax of the distribution for
        position i + 1."""
        import jax
        import jax.numpy as jnp

        L = int(tokens.shape[0])
        bucket = None
        for b in self._buckets:
            if L <= b:
                bucket = b
                break
        if bucket is None:
            raise ValueError(
                f"stream length {L} exceeds the speculative max context "
                f"{self.max_context}")
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :L] = tokens
        logits, _last, _cache = model.executor.make_prefill_step(
            bucket, bucket)(model.params, [jnp.asarray(ids)],
                            jnp.asarray([L], np.int32))
        # reduce on device BEFORE the transfer (ISSUE 17 satellite):
        # only the argmax ids are consumed, so ship (bucket,) int32
        # instead of the full padded (1, bucket, vocab) float matrix —
        # vocab x 4 bytes fewer per scored position, every round
        if self._argmax is None:
            self._argmax = jax.jit(
                lambda lg: jnp.argmax(lg[0], axis=-1).astype(jnp.int32))
        ids_out = self._argmax(logits)
        return np.asarray(jax.device_get(ids_out))[:L]

    # ------------------------------------------------------------ generate
    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32, temperature: float = 0.0,
                 eos_id: Optional[int] = None) -> List[List[int]]:
        """Generate greedy continuations; token-identical to the
        baseline engine's greedy output (the two streams are equal in
        tier-1, tests/test_decode_paged.py), at
        ~``(accepted + 1)`` tokens per target forward."""
        if temperature > 0.0:
            raise NotImplementedError(
                "speculative decoding is greedy-only: temperature "
                "sampling needs the rejection-sampling correction to "
                "stay distribution-identical; decode through "
                "ServingEngine.generate instead")
        out: List[List[int]] = []
        for p in prompts:
            out.append(self._generate_one(
                np.asarray(p, np.int32), int(max_new_tokens), eos_id))
        return out

    def _generate_one(self, prompt: np.ndarray, max_new: int,
                      eos_id: Optional[int]) -> List[int]:
        stats = self.stats
        stream = list(int(t) for t in prompt)
        generated: List[int] = []
        while len(generated) < max_new:
            t0 = time.perf_counter()
            room = min(max_new - len(generated),
                       self.max_context - len(stream))
            if room <= 0:
                break
            # propose: up to gamma greedy drafter tokens (gamma+draft
            # must still fit the context for the verification pass)
            g = min(self.gamma, room - 1) if room > 1 else 0
            draft: List[int] = []
            ds = list(stream)
            for _ in range(g):
                nxt = int(self._score(self.drafter,
                                      np.asarray(ds, np.int32))[-1])
                draft.append(nxt)
                ds.append(nxt)
                if eos_id is not None and nxt == int(eos_id):
                    break
            # verify: ONE target pass over stream + draft scores every
            # draft position AND the bonus position
            preds = self._score(self.target,
                                np.asarray(stream + draft, np.int32))
            L = len(stream)
            accepted = 0
            commits: List[int] = []
            for i, d in enumerate(draft):
                t_pred = int(preds[L - 1 + i])
                if t_pred == d:
                    accepted += 1
                    commits.append(d)
                else:
                    commits.append(t_pred)  # the correction token
                    break
            else:
                # every draft token accepted: the verification pass
                # already scored position L + len(draft) — a free token
                commits.append(int(preds[L - 1 + len(draft)]))
            wall = time.perf_counter() - t0
            stats.wall_s += wall
            stats.spec_rounds += 1
            stats.spec_proposed += len(draft)
            stats.spec_accepted += accepted
            committed_now = 0
            for tok in commits:
                if len(generated) >= max_new:
                    break
                generated.append(tok)
                stream.append(tok)
                committed_now += 1
                stats.tokens_generated += 1
                stats.record_token(wall / max(len(commits), 1))
                if eos_id is not None and tok == int(eos_id):
                    break
            if self.controller is not None and committed_now:
                self.controller.observe_step(wall, committed_now)
                self.controller.observe_speculation(accepted, len(draft))
            if eos_id is not None and generated and \
                    generated[-1] == int(eos_id):
                break
            if committed_now == 0:
                break  # context exhausted mid-round
        stats.requests_served += 1
        return generated
