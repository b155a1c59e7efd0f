"""Rollout plane: generator actors hosting a logprob-capturing engine.

Each `RolloutWorker` owns one `LLMEngine` —
continuous batching, registered-prefix KV reuse for the shared system
prompt, and per-token logp capture at sampling time (the GRPO ratio
term's old-policy logps, recorded for free instead of recomputed with
a second forward). `rollout()` fans a prompt batch through the engine
and returns fixed-shape numpy buffers the learner shards directly;
`refresh_weights()` swaps in a new policy from relay-broadcast param
blocks.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..models.transformer import TransformerConfig, init_params
from ..observability import get_recorder
from ..util import tracing as _tracing


class RolloutWorker:
    """Generator actor for the RLHF pipeline (run via ray_tpu.remote).

    Starts from a seed-initialized policy; the pipeline's first weight
    refresh overwrites it with the learner's, so generation and update
    always run the same weights (versioned — every rollout result
    carries the policy version it sampled from).
    """

    def __init__(self, cfg: TransformerConfig, *, num_slots: int = 4,
                 seed: int = 0, decode_block: int = 16,
                 system_prompt: Optional[Sequence[int]] = None):
        import jax

        from ..serve.llm import LLMEngine

        self.cfg = cfg
        params = init_params(cfg, jax.random.key(seed))
        self.engine = LLMEngine(cfg, params, num_slots=num_slots,
                                seed=seed, decode_block=decode_block)
        self._version = -1  # seed weights; refresh installs version >= 0
        self._refresh_bytes = 0
        self._inject_delay_s = 0.0
        if system_prompt:
            self.engine.register_prefix(list(system_prompt))

    def inject_fault(self, kind: str, value) -> None:
        """Chaos hook (same contract as serve Replica.inject_fault):
        `rollout_delay_s` makes this generator a deterministic
        straggler — every rollout sleeps first, the slow-node shape
        the anomaly watchdog must flag."""
        if kind == "rollout_delay_s":
            self._inject_delay_s = float(value)
        else:
            raise ValueError(f"unknown fault kind: {kind}")

    # -- weight refresh ------------------------------------------------

    def refresh_weights(self, version: int, *blocks) -> Dict[str, Any]:
        """Install policy `version` from param blocks ((leaf index,
        array) pairs, any split). Blocks arrive as VALUES — the caller
        passes ObjectRefs and the runtime's arg plane resolves them,
        which on a daemon cluster is exactly the relay-broadcast pull
        path (each node fetches from its tree parent, not the
        producer)."""
        import jax

        t0 = time.perf_counter()
        pairs: List = []
        for block in blocks:
            pairs.extend(block)
        leaves = jax.tree.leaves(self.engine.params)
        if len(pairs) != len(leaves):
            raise ValueError(
                f"weight refresh v{version}: got {len(pairs)} leaves, "
                f"policy has {len(leaves)}")
        by_idx = dict(pairs)
        treedef = jax.tree.structure(self.engine.params)
        new_params = jax.tree.unflatten(
            treedef, [by_idx[i] for i in range(len(leaves))])
        self.engine.set_params(new_params)
        self._version = int(version)
        nbytes = sum(np.asarray(a).nbytes for _, a in pairs)
        self._refresh_bytes += nbytes
        dt = time.perf_counter() - t0
        get_recorder().record("rlhf", "weight_refresh",
                              version=int(version), bytes=nbytes,
                              seconds=dt)
        return {"version": self._version, "bytes": nbytes,
                "seconds": dt}

    def weight_version(self) -> int:
        return self._version

    # -- generation ----------------------------------------------------

    def rollout(self, prompts: np.ndarray, *, group_size: int = 1,
                max_new_tokens: int = 16, temperature: float = 1.0,
                eos_token: Optional[int] = None,
                seed: Optional[int] = None) -> Dict[str, Any]:
        """prompts (n, P) int32 → G completions per prompt.

        Returns fixed-shape buffers (N = n * group_size, S = P +
        max_new_tokens, group-major order): "seqs" (N, S) full
        sequences zero-padded past each completion, "logprobs" (N,
        max_new) sampling-time logp per generated token, "lengths"
        (N,) completion lengths, and the policy "version" sampled
        from."""
        if self._inject_delay_s > 0:
            time.sleep(self._inject_delay_s)
        prompts = np.asarray(prompts, np.int32)
        n, P = prompts.shape
        grouped = np.repeat(prompts, group_size, axis=0)
        N = n * group_size
        S = P + max_new_tokens

        with _tracing.span("rlhf.rollout", prompts=n,
                           group_size=group_size):
            t0 = time.perf_counter()
            reqs = [self.engine.submit(
                grouped[i].tolist(), max_new_tokens=max_new_tokens,
                temperature=temperature, eos_token=eos_token)
                for i in range(N)]
            while any(r.finish_ts == 0.0 for r in reqs):
                self.engine.step()
            gen_s = time.perf_counter() - t0

        seqs = np.zeros((N, S), np.int32)
        seqs[:, :P] = grouped
        logprobs = np.zeros((N, max_new_tokens), np.float32)
        lengths = np.zeros((N,), np.int32)
        for i, r in enumerate(reqs):
            toks = r.tokens
            L = len(toks)
            seqs[i, P:P + L] = toks
            logprobs[i, :L] = r.logprobs
            lengths[i] = L
        tokens_out = int(lengths.sum())
        get_recorder().record("rlhf", "rollout", sequences=N,
                              tokens=tokens_out, seconds=gen_s,
                              version=self._version)
        return {"seqs": seqs, "logprobs": logprobs, "lengths": lengths,
                "prompt_len": P, "tokens": tokens_out,
                "gen_s": gen_s, "version": self._version}

    def stats(self) -> Dict[str, Any]:
        return {"version": self._version,
                "refresh_bytes": self._refresh_bytes,
                "tokens_out": self.engine.tokens_out,
                "prefix_hits": self.engine.prefix_hits}

    def node_id(self) -> str:
        """Scheduling evidence for the cluster tests."""
        from .. import get_runtime_context

        return get_runtime_context().get_node_id()
