"""Driver of ``kind: replica`` configurations: the served model's replica
step (``build_model(cfg, impl="pallas")``'s ``prefill`` and
``decode_step``) under closed-loop rounds of requests.

A round is ``batch`` requests that arrive together with ``prompt``-token
prompts: one prefill emits each request's first token, then decode steps
emit one token per request until each has ``output`` tokens.  Decoding is
greedy.  The host dispatches decode steps ahead of the device and reads
their tokens every ``SYNC_STEPS`` steps (about half a second), so no step
waits on the host.  The window starts at the start of a round and ends at
the first read of tokens past ``seconds``; every token emitted in the
window counts, so a round cut by the window's end is not lost.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from reference import lm

# decode steps dispatched between two reads of their tokens
SYNC_STEPS = 16

# the configuration file's keys that are the program's ModelConfig fields
MODEL_KEYS = ("name", "family", "num_layers", "d_model", "num_heads",
              "num_kv_heads", "head_dim", "d_ff", "vocab_size", "rope_theta",
              "parallel_block", "tie_embeddings", "act", "norm_eps")


class Driver:
    """Rounds of one traffic file through one model configuration."""

    # host annotations the trace keeps, to name idle gaps
    ANNOTATIONS = ("prefill", "decode")
    # a traced run measures the run's whole window
    TRACED_SECONDS = None

    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 limits: Dict) -> None:
        import jax
        import jax.numpy as jnp

        from repro.models import build_model
        from repro.models.config import ModelConfig

        self.config, self.traffic, self.seed = config, traffic, seed
        self.limits = limits
        self.cfg = ModelConfig(**{k: config[k] for k in MODEL_KEYS})
        self.model = build_model(self.cfg, impl="pallas")
        B, S = traffic["batch"], traffic["prompt"]
        self.B, self.S, self.out = B, S, traffic["output"]
        self.slots = traffic["cache_slots"]
        V = self.cfg.vocab_size
        model = self.model

        def prefill(params, tokens, cache):
            logits, cache = model.prefill(params, tokens, cache)
            return jnp.argmax(logits[:, -1, :V], -1).astype(jnp.int32)[
                :, None], cache

        def decode(params, tok, cache):
            logits, cache = model.decode_step(params, tok, cache)
            return jnp.argmax(logits[:, -1, :V], -1).astype(jnp.int32)[
                :, None], cache

        self._prefill = jax.jit(prefill, donate_argnums=(2,))
        self._decode = jax.jit(decode, donate_argnums=(2,))
        prompt_key = lm._seed_parts(seed)

        @jax.jit
        def prompts(r):
            k = jax.random.fold_in(lm._key_parts(prompt_key), 1_000_003 + r)
            return jax.random.randint(k, (B, S), 0, V, jnp.int32)

        self._prompts = prompts
        self.params = None
        self.cache = None
        self.rounds: List[Dict] = []
        self.readings: Dict = {}

    # -- set-up -------------------------------------------------------
    def setup(self) -> None:
        import jax

        params = lm.serving_params(self.config, self.seed)
        want = jax.tree_util.tree_map(
            lambda a: (a.shape, str(a.dtype)),
            self.model.abstract(params["embed"].dtype))
        got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                                     params)
        if got != want:
            raise SystemExit("the drawn weights do not have the program's "
                             f"parameter layout: {got} != {want}")
        self.params = params
        self.cache = self.model.init_cache(self.B, self.slots)
        # every program the window runs, once
        tok, self.cache = self._prefill(self.params, self._prompts(0),
                                        self.cache)
        tok, self.cache = self._decode(self.params, tok, self.cache)
        jax.block_until_ready((tok, self.cache))

    # -- window -------------------------------------------------------
    def window(self, seconds: float) -> Dict:
        import jax

        rounds: List[Dict] = []
        emitted = 0
        t_start = time.perf_counter()
        r = 0
        done = False
        while not done:
            prompts = self._prompts(r)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("prefill"):
                tok, self.cache = self._prefill(self.params, prompts,
                                                self.cache)
                tok.block_until_ready()
            t1 = time.perf_counter()
            toks = [tok]
            emitted += self.B
            done = t1 - t_start >= seconds
            with jax.profiler.TraceAnnotation("decode"):
                while not done and len(toks) < self.out:
                    n = min(SYNC_STEPS, self.out - len(toks))
                    for _ in range(n):
                        tok, self.cache = self._decode(self.params, tok,
                                                       self.cache)
                        toks.append(tok)
                    tok.block_until_ready()
                    emitted += self.B * n
                    done = time.perf_counter() - t_start >= seconds
            t2 = time.perf_counter()
            rounds.append({
                "round": r, "prompts": prompts, "served": toks,
                "prefill_s": t1 - t0, "decode_s": t2 - t1,
                "decode_steps": len(toks) - 1,
            })
            r += 1
        elapsed = time.perf_counter() - t_start
        self.rounds = rounds
        steps = sum(x["decode_steps"] for x in rounds)
        decode_s = sum(x["decode_s"] for x in rounds)
        self.readings = {
            "window_s": elapsed,
            "tokens": emitted,
            "prefill_s": [x["prefill_s"] for x in rounds],
            "calls": [(self.B, self.S, x["decode_steps"]) for x in rounds],
        }
        metrics = {"tokens_per_s": emitted / elapsed}
        if steps:
            metrics["itl_ms_mean"] = 1e3 * decode_s / steps
        return {"attempted": self.B * len(rounds), "failed": 0,
                "metrics": metrics}

    def free(self) -> None:
        """Drops the program's weights and cache before the reference."""
        self.params = None
        self.cache = None

    # -- correctness --------------------------------------------------
    def sample(self, n: int) -> List[np.ndarray]:
        """``n`` requests drawn from the seed among those of the longest
        round (a finished one, where any finished), each as its prompt
        followed by its served tokens."""
        best = max(self.rounds, key=lambda x: len(x["served"]))
        rng = np.random.default_rng(self.seed + 7)
        rows = rng.choice(self.B, size=min(n, self.B), replace=False)
        prompts = np.asarray(best["prompts"])
        # joined on the host: a device concatenate would compile anew for
        # every round length
        served = np.concatenate([np.asarray(t) for t in best["served"]], 1)
        return [np.concatenate([prompts[i], served[i]]) for i in rows]

    def gaps(self, seqs: List[np.ndarray], precision: str = "f32"):
        """Per sequence, the gap of each served token below the
        reference's best logit at its position; with ``precision="fp8"``
        the gap of the token the control puts first instead."""
        out = []
        for seq in seqs:
            ref = np.asarray(lm.logits(self.config, self.seed, seq[:-1]))
            ref = ref[self.S - 1:]
            if precision == "f32":
                pick = seq[self.S:]
            else:
                low = np.asarray(lm.logits(self.config, self.seed, seq[:-1],
                                           precision))
                pick = low[self.S - 1:].argmax(-1)
            out.append(ref.max(-1) - ref[np.arange(len(pick)), pick])
        return out

    def check(self) -> Dict[str, Dict]:
        """The widest gap of a served token below the reference's best
        logit, over the sampled requests."""
        seqs = self.sample(self.traffic["check_requests"])
        worst = max(float(g.max()) for g in self.gaps(seqs))
        return {"logit_gap": {"value": worst,
                              "limit": self.limits["logit_gap"]}}

    def control(self) -> Dict[str, float]:
        """The widest gap of the token that the float8 control puts first,
        on the same requests."""
        seqs = self.sample(self.traffic["check_requests"])
        return {"logit_gap": max(float(g.max())
                                 for g in self.gaps(seqs, "fp8"))}
