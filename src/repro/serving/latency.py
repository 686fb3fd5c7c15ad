"""Request latency models per (model config × instance): roofline + profiled.

The paper's Fig. 6a decomposes a Vicuna-13B request: model execution time
(prefill + per-token decode) dominates; network RTT is tens of ms.  We
reproduce that structure analytically so the serving simulator's service
times are grounded in the same hardware model as the §Roofline analysis:

    prefill_s(P)      = 2·N·P FLOPs / (accels × peak_flops × MFU_prefill)
    decode_s_per_tok  = weight bytes / (accels × HBM_bw) / MBU_decode
    service_s(req)    = prefill + out_tokens × decode + overhead

Prefill is compute-bound; decode is HBM-bound (weights re-read per
token).  :class:`LatencyModel` uses literature-typical efficiency
constants (MFU ~0.45 on a tuned engine, MBU ~0.7);
:class:`ProfiledLatencyModel` replaces those constants with efficiencies
*measured* on this repo's Pallas kernels by ``repro.profiles`` — same
roofline structure, measured numerator.  ``make_latency_model`` picks
between them from a ``ServiceSpec``'s ``latency:`` section, falling back
to the analytic roofline when no profile entry matches, so default runs
(and the golden metrics) are byte-identical with or without profile
artifacts on disk.

Peak HBM bandwidth lives on :class:`repro.cluster.catalog.InstanceType`
(resolved from ``ACCEL_HBM_BYTES_PER_S`` by accelerator name — unknown
accelerators raise at catalog construction instead of silently serving
from a guessed 0.8 TB/s part).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, TYPE_CHECKING

from repro.cluster.catalog import InstanceType
from repro.models.config import ModelConfig

if TYPE_CHECKING:  # pragma: no cover - import cycle guard (typing only)
    from repro.profiles.schema import ProfileEntry

__all__ = [
    "LatencyModel",
    "ProfiledLatencyModel",
    "make_latency_model",
]


@dataclasses.dataclass(frozen=True)
class LatencyModel:
    cfg: ModelConfig
    itype: InstanceType
    n_params: float
    mfu_prefill: float = 0.45
    mbu_decode: float = 0.70
    overhead_s: float = 0.05        # tokenize/detokenize/HTTP

    @classmethod
    def for_model(cls, cfg: ModelConfig, itype: InstanceType,
                  n_params: float = 0.0) -> "LatencyModel":
        n = n_params or float(cfg.approx_params())
        return cls(cfg=cfg, itype=itype, n_params=n)

    # ------------------------------------------------------------------
    @property
    def _active_params(self) -> float:
        cfg = self.cfg
        if not cfg.is_moe:
            return self.n_params
        expert = (
            cfg.num_layers * cfg.num_experts
            * (3 if cfg.mlp_gated else 2) * cfg.d_model * cfg.expert_d_ff
        )
        return self.n_params - expert * (
            1.0 - cfg.experts_per_token / cfg.num_experts
        )

    @property
    def flops_per_s(self) -> float:
        return (
            self.itype.accel_count
            * self.itype.peak_bf16_tflops * 1e12
            * self.mfu_prefill
        )

    @property
    def hbm_bytes_per_s(self) -> float:
        # peak per-accelerator bandwidth comes from the instance catalog
        # (cluster.catalog.ACCEL_HBM_BYTES_PER_S keyed by accelerator)
        return (
            self.itype.accel_count
            * self.itype.hbm_bytes_per_s
            * self.mbu_decode
        )

    # ------------------------------------------------------------------
    def prefill_s(self, prompt_tokens: int) -> float:
        return 2.0 * self._active_params * prompt_tokens / self.flops_per_s

    def decode_s_per_token(self) -> float:
        weight_bytes = 2.0 * self._active_params     # bf16
        return weight_bytes / self.hbm_bytes_per_s

    def service_s(self, prompt_tokens: int, output_tokens: int) -> float:
        return (
            self.overhead_s
            + self.prefill_s(prompt_tokens)
            + output_tokens * self.decode_s_per_token()
        )

    # ------------------------------------------------------------------
    def kv_bytes_per_token(self, ctx: Optional[int] = None) -> float:
        """K+V bf16 bytes a cached token occupies, averaged over a sequence
        of ``ctx`` tokens (None: one no window fills).  Only attention
        layers keep KV (a hybrid's shared attention blocks, not its mamba
        layers), and a window layer keeps at most its window, so past it
        a token costs only the full layers.  0: no KV cache."""
        cfg = self.cfg
        if not (cfg.num_kv_heads and cfg.resolved_head_dim):
            return 0.0
        layer = 2 * cfg.num_kv_heads * cfg.resolved_head_dim * 2
        attn = (cfg.hybrid_blocks if cfg.family == "hybrid"
                else cfg.num_layers)
        windowed = cfg.window_layers
        if ctx is None or cfg.sliding_window is None:
            return float(layer * attn)
        held = min(ctx, cfg.sliding_window) / ctx
        return float(layer * ((attn - windowed) + windowed * held))

    def free_kv_hbm_bytes(self) -> float:
        """HBM left for KV cache: 90% usable minus bf16 weights,
        floored at 5% (the shared budget arithmetic — also feeds the
        token engine's ``kv_budget_tokens``)."""
        hbm = (
            self.itype.accel_count * self.itype.hbm_gib_per_accel * 2**30
        )
        weights = 2.0 * self.n_params
        return max(hbm * 0.9 - weights, hbm * 0.05)

    def max_concurrency(self, max_ctx: int = 4096) -> int:
        """Requests servable concurrently from leftover HBM (KV budget).
        Attention-free archs are compute-limited instead (use 32)."""
        kv_seq = self.kv_bytes_per_token(max_ctx) * max_ctx
        if kv_seq:
            return max(1, int(self.free_kv_hbm_bytes() / kv_seq))
        return 32


@dataclasses.dataclass(frozen=True)
class ProfiledLatencyModel(LatencyModel):
    """Roofline latency with kernel-measured MFU/MBU.

    Identical service-time structure to :class:`LatencyModel`; the
    ``mfu_prefill`` / ``mbu_decode`` efficiency fractions come from a
    ``repro.profiles`` step-time table instead of hand-waved constants.
    Provenance rides along so a result can always answer "which profile
    priced this run, measured where, in which mode".
    """

    profile_path: str = ""
    profile_backend: str = ""       # jax backend the measurement ran on
    profile_mode: str = ""          # "interpret" | "compiled"

    @classmethod
    def from_entry(
        cls,
        cfg: ModelConfig,
        itype: InstanceType,
        entry: "ProfileEntry",
        *,
        path: str = "",
        n_params: float = 0.0,
    ) -> "ProfiledLatencyModel":
        n = n_params or float(cfg.approx_params())
        return cls(
            cfg=cfg,
            itype=itype,
            n_params=n,
            mfu_prefill=entry.mfu_prefill,
            mbu_decode=entry.mbu_decode,
            profile_path=path,
            profile_backend=entry.backend,
            profile_mode=entry.mode,
        )


LATENCY_SOURCES = ("roofline", "profile")

def make_latency_model(
    cfg: ModelConfig,
    itype: InstanceType,
    *,
    model_id: str,
    source: str = "roofline",
    profile: Optional[str] = None,
) -> LatencyModel:
    """Build the latency model a ``ServiceSpec``'s ``latency:`` asks for.

    ``source="roofline"`` (the default) is the analytic model —
    bit-identical to the historical behaviour.  ``source="profile"``
    loads the step-time table(s) at ``profile`` (a JSON file or a
    directory of them; defaults to ``artifacts/profiles/``) and looks up
    ``(model_id, itype.accelerator)``; when no table or no matching entry
    exists it *warns and falls back to the roofline* rather than failing
    the run, so specs stay portable across machines with and without
    profile artifacts.
    """
    if source not in LATENCY_SOURCES:
        raise ValueError(
            f"latency source must be one of {list(LATENCY_SOURCES)}, "
            f"got {source!r}"
        )
    if source == "roofline":
        return LatencyModel.for_model(cfg, itype)

    from repro.profiles.schema import DEFAULT_PROFILE_DIR, load_profiles

    path = profile or DEFAULT_PROFILE_DIR
    table = load_profiles(path, missing_ok=True)
    entry = table.lookup(model_id, itype.accelerator)
    if entry is None:
        # run-scoped counter (repro.obs): warnings scroll away, this
        # lands on the calling run's registry — sweeps and tests can
        # assert a run stayed on measured profiles without cross-run
        # bleed from a process-global tally
        from repro.obs.registry import get_registry

        get_registry().inc(
            "latency_profile_fallback",
            model=model_id,
            accelerator=itype.accelerator,
        )
        warnings.warn(
            f"latency source 'profile': no profile entry for "
            f"({model_id!r}, {itype.accelerator!r}) under {path!r}; "
            "falling back to the analytic roofline model",
            stacklevel=2,
        )
        return LatencyModel.for_model(cfg, itype)
    return ProfiledLatencyModel.from_entry(cfg, itype, entry, path=str(path))
