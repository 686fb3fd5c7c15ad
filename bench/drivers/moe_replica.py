"""Driver of ``kind: moe_replica`` configurations: a mixture-of-experts
model of window and full attention layers, whose chip holds a share of
each layer's experts, served through ``build_model(cfg, impl="pallas")``'s
``prefill`` and ``decode_step`` under the closed-loop rounds of
``drivers/replica.py``, with one difference: the window holds whole
rounds.  A round started before ``seconds`` have passed runs to its end,
so every request of the window gets all its output tokens, decode runs
at every context of the traffic, and prefill takes its share of a real
round.

The configuration file carries the published ``config.json``'s keys;
``model_config`` reads the program's ``ModelConfig`` from them
(``reference.moe_lm.shape``).  The weights and the reference are
``reference/moe_lm.py``'s.  Besides the replica's readings, the window
leaves ``moe_load``: the assignments each held expert of each layer
received in the window, read from the program's counter once, after it.

``check`` compares two numbers of the served tokens' gaps below the
reference's best logit: the widest (``logit_gap``, as the replica
driver) and the mean (``logit_gap_mean``).  A sound bfloat16 program
picks the reference's best token but where two logits nearly tie (and
where routing flips at the boundary of the top experts), so its gaps are
rare; the float8 control's are not, though its widest need not be wider
than a sound run's rarest.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from drivers import replica
from reference import lm, moe_lm


def model_config(config: Dict):
    """The program's ``ModelConfig`` of a configuration file."""
    from repro.models.config import ModelConfig, YaRN

    s = moe_lm.shape(config)
    factor, orig, fast, slow, attention = s["yarn"]
    return ModelConfig(
        name=config["name"], family="moe", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["heads"], num_kv_heads=s["kv"],
        head_dim=s["hd"], d_ff=config["intermediate_size"],
        vocab_size=s["vocab"], rope_theta=float(s["theta"]),
        sliding_window=s["window"], full_attn_every=s["period"],
        full_attn_yarn=YaRN(factor=float(factor), original_max_position=orig,
                            beta_fast=float(fast), beta_slow=float(slow),
                            attention_factor=attention),
        qk_norm=config["qk_norm"], num_experts=s["experts"],
        experts_per_token=s["top_k"], moe_d_ff=s["f"],
        experts_held=s["held"], expert_offset=s["offset"],
        tie_embeddings=config["tie_word_embeddings"],
        act=config["hidden_act"], norm_eps=s["eps"],
    )


class Driver(replica.Driver):
    """Rounds of one traffic file through one MoE model configuration."""

    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 limits: Dict) -> None:
        import jax
        import jax.numpy as jnp

        from repro.models import build_model

        self.config, self.traffic, self.seed = config, traffic, seed
        self.limits = limits
        self.cfg = model_config(config)
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
        import jax.numpy as jnp

        params = moe_lm.serving_params(self.config, self.seed)
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
        # the window's counts start from zero
        self.cache["moe_load"] = jnp.zeros_like(self.cache["moe_load"])

    # -- window -------------------------------------------------------
    def window(self, seconds: float) -> Dict:
        import jax

        rounds: List[Dict] = []
        t_start = time.perf_counter()
        r = 0
        while not rounds or time.perf_counter() - t_start < seconds:
            prompts = self._prompts(r)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("prefill"):
                tok, self.cache = self._prefill(self.params, prompts,
                                                self.cache)
                tok.block_until_ready()
            t1 = time.perf_counter()
            toks = [tok]
            with jax.profiler.TraceAnnotation("decode"):
                while len(toks) < self.out:
                    for _ in range(min(replica.SYNC_STEPS,
                                       self.out - len(toks))):
                        tok, self.cache = self._decode(self.params, tok,
                                                       self.cache)
                        toks.append(tok)
                    tok.block_until_ready()
            t2 = time.perf_counter()
            rounds.append({
                "round": r, "prompts": prompts, "served": toks,
                "prefill_s": t1 - t0, "decode_s": t2 - t1,
                "decode_steps": len(toks) - 1,
            })
            r += 1
        elapsed = time.perf_counter() - t_start
        self.rounds = rounds
        self.readings = {
            "window_s": elapsed,
            "tokens": self.B * self.out * len(rounds),
            "prefill_s": [x["prefill_s"] for x in rounds],
            "calls": [(self.B, self.S, x["decode_steps"]) for x in rounds],
            "moe_load": np.asarray(self.cache["moe_load"]),
        }
        return {"attempted": self.B * len(rounds), "failed": 0,
                "metrics": {"tokens_per_s":
                            self.readings["tokens"] / elapsed}}

    # -- correctness --------------------------------------------------
    def gaps(self, seqs: List[np.ndarray], precision: str = "f32"):
        """Per sequence, the gap of each served token below the
        reference's best logit at its position; with ``precision="fp8"``
        the gap of the token the control puts first instead."""
        out = []
        for seq in seqs:
            ref = np.asarray(moe_lm.logits(self.config, self.seed,
                                           seq[:-1]))
            ref = ref[self.S - 1:]
            if precision == "f32":
                pick = seq[self.S:]
            else:
                low = np.asarray(moe_lm.logits(self.config, self.seed,
                                               seq[:-1], precision))
                pick = low[self.S - 1:].argmax(-1)
            out.append(ref.max(-1) - ref[np.arange(len(pick)), pick])
        return out

    def check(self) -> Dict[str, Dict]:
        """The widest and the mean gap of a served token below the
        reference's best logit, over the sampled requests."""
        gaps = np.concatenate(self.gaps(
            self.sample(self.traffic["check_requests"])))
        return {name: {"value": value, "limit": self.limits[name]}
                for name, value in _stats(gaps).items()}

    def control(self) -> Dict[str, float]:
        """The same numbers of the token the float8 control puts first,
        on the same requests."""
        return _stats(np.concatenate(self.gaps(
            self.sample(self.traffic["check_requests"]), "fp8")))


def _stats(gaps: np.ndarray) -> Dict[str, float]:
    return {"logit_gap": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean())}
