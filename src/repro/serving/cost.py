"""Per-request LLM inference cost from the roofline machinery.

Bridges the repo's two halves: the analytic LM cost model
(``launch/roofline.py`` — parameter counts via cheap ``jax.eval_shape``,
MoE active-parameter discounts, the 2·N flop/token serving rule) and the
paper's AP machine model (``core/models.py``).  For one ``configs/``
entry and a request shape it produces

* per-request prefill/decode FLOPs and the per-decode-step byte
  traffic (weight stream + per-sequence KV/state reads, the
  ``models/serve.py`` batching semantics: one weight read per step is
  amortized over the whole decode batch);
* the decode arithmetic intensity AI(B) [flop/word] as a function of
  batch size — batching raises AI because the parameter stream is
  shared;
* a :class:`~repro.core.models.Workload` minted from that AI by the
  same inverse-AI anchoring the suite workloads use
  (``models.derived_workload``), which gives the serving scenario its
  same-performance AP/SIMD design pair and DRAM-traffic figure.

Costs by mechanism, each derived from the config (``cfg.moe``,
``cfg.mla``) and the model's parameter shapes, with no branch on the
model's name:

* **Routed experts at batch B.**  A decode step streams every routed
  expert that any of its B tokens routes to.  Under uniform routing
  (each token picks k of E experts at random; a random router is close
  to that, tests/test_serving_moe_mla.py) a MoE layer touches
  E·(1 − (1 − k/E)^B) experts, k at B = 1 and all E as B grows.
  Shared experts, dense layers, attention, norms, embedding and head
  are read once a step.
* **Latent attention (MLA).**  Decode runs the absorbed form: per
  layer and context position 2H(kv_lora + qk_rope) FLOPs of scores over
  the latent and the rope key and 2H·kv_lora of values.  Prefill runs
  the expanded form: 2H(qk_nope + qk_rope) + 2H·v_dim FLOPs per layer
  and causal (query, key) pair, P(P+1)/2 pairs a prompt.  Attention
  FLOPs of non-MLA configs are not counted (they stay on the
  2·N_active rule); their KV bytes are.
"""
from __future__ import annotations

import dataclasses
import functools

from repro.core import models as M

BYTES_PER_PARAM = 2.0          # bf16 serving weights (launch/steps.py dtype)
KV_BYTES_PER_EL = 2.0          # bf16 KV cache entries


@dataclasses.dataclass(frozen=True)
class RequestShape:
    """One request class: prompt length in, generated tokens out."""
    prompt_tokens: int = 1024
    output_tokens: int = 128

    def __post_init__(self):
        if self.prompt_tokens < 1 or self.output_tokens < 1:
            raise ValueError("prompt/output tokens must be >= 1")


def kv_bytes_per_token(cfg) -> float:
    """Per-token KV-cache footprint in bytes (what each decode step
    re-reads per sequence per context token).

    MLA configs cache the compressed latent (kv_lora + rope dims);
    attention-free SSM blocks keep O(1) state per sequence, so their
    per-context-token cost is 0; hybrids pay only for the shared
    attention blocks (one per ``attn_every`` layers).
    """
    if cfg.family == "ssm":
        return 0.0
    if cfg.mla is not None:
        per_layer = cfg.mla.kv_lora + cfg.mla.qk_rope
    else:
        per_layer = 2 * cfg.n_kv_heads * cfg.head_dim
    if cfg.family == "hybrid":
        n_attn = max(cfg.n_layers // max(cfg.attn_every, 1), 1)
    else:
        n_attn = cfg.n_layers
    return float(n_attn * per_layer * KV_BYTES_PER_EL)


def experts_touched(n_experts: int, top_k: int, batch: int) -> float:
    """Expected routed experts one MoE layer streams at decode batch B."""
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** batch)


def mla_attn_flops(cfg) -> tuple[float, float]:
    """(decode FLOPs per token per context position, prefill FLOPs per
    causal (query, key) pair), summed over the layers; (0, 0) for
    configs without latent attention."""
    m = cfg.mla
    if m is None:
        return 0.0, 0.0
    two_h = 2.0 * cfg.n_heads
    decode = two_h * (m.kv_lora + m.qk_rope) + two_h * m.kv_lora
    prefill = two_h * (m.qk_nope + m.qk_rope) + two_h * m.v_dim
    return cfg.n_layers * decode, cfg.n_layers * prefill


@dataclasses.dataclass(frozen=True)
class ModelServingCost:
    """Analytic serving cost of one config for one request shape.

    The per-mechanism counts default to a dense, non-MLA model: no
    routed experts and no attention FLOPs over the context.
    """
    config: str
    request: RequestShape
    n_params: float             # total parameters
    n_active: float             # active per token (MoE top-k discount)
    kv_bytes_tok: float         # KV bytes per context token per sequence
    routed_params_layer: float = 0.0    # routed-expert params, one layer
    n_moe_layers: int = 0
    n_experts: int = 0          # routed experts per MoE layer (E)
    top_k: int = 0              # routed experts per token (k)
    attn_decode_flops_ctx: float = 0.0  # per token per context position
    attn_prefill_flops_pair: float = 0.0    # per causal (q, k) pair

    # ------------------------------------------------------------- flops
    @property
    def attn_prefill_flops(self) -> float:
        """Latent-attention FLOPs of one prompt: P(P+1)/2 causal pairs."""
        p = self.request.prompt_tokens
        return self.attn_prefill_flops_pair * p * (p + 1) / 2.0

    @property
    def attn_flops_per_token(self) -> float:
        """Latent-attention FLOPs of one decode token at the mean
        context."""
        return self.attn_decode_flops_ctx * self.mean_context

    @property
    def prefill_flops(self) -> float:
        """2·N_active per prompt token (launch/roofline.py serving rule)
        plus the prompt's latent attention."""
        return 2.0 * self.n_active * self.request.prompt_tokens \
            + self.attn_prefill_flops

    @property
    def decode_flops_per_token(self) -> float:
        return 2.0 * self.n_active + self.attn_flops_per_token

    @property
    def request_flops(self) -> float:
        """Total useful FLOPs to serve one request end to end."""
        return self.prefill_flops \
            + self.decode_flops_per_token * self.request.output_tokens

    # ------------------------------------------------------------- bytes
    @property
    def param_bytes(self) -> float:
        """Weight stream of one decode step at batch 1 (active
        parameters)."""
        return BYTES_PER_PARAM * self.n_active

    @property
    def mean_context(self) -> float:
        """Average live context length during decode."""
        return self.request.prompt_tokens + self.request.output_tokens / 2.0

    def experts_touched(self, batch: int) -> float:
        """Routed experts one MoE layer streams at decode batch B."""
        if self.n_experts == 0:
            return 0.0
        return experts_touched(self.n_experts, self.top_k, batch)

    def weight_bytes_per_step(self, batch: int) -> float:
        """Weight stream of one decode step at batch B: everything but
        the routed experts once, and each MoE layer's touched experts.

        Written as the batch-1 active count plus the experts touched
        beyond k, so batch 1 is `param_bytes` to the last bit."""
        if batch < 1 or batch != int(batch):
            raise ValueError("batch must be a whole number >= 1")
        extra = 0.0
        if self.n_experts:
            miss = 1.0 - self.top_k / self.n_experts
            extra = self.routed_params_layer * self.n_moe_layers \
                * (miss - miss ** batch)
        return BYTES_PER_PARAM * (self.n_active + extra)

    def kv_bytes_per_step(self, batch: int) -> float:
        """Per-sequence KV/state reads of one decode step at batch B."""
        return batch * self.kv_bytes_tok * self.mean_context

    def decode_step_bytes(self, batch: int) -> float:
        """DRAM bytes of one decode step at batch size B: one shared
        weight read + per-sequence KV/state reads."""
        return self.weight_bytes_per_step(batch) \
            + self.kv_bytes_per_step(batch)

    def decode_ai(self, batch: int) -> float:
        """Decode arithmetic intensity at batch B [flop/word] — rises
        with B while the shared parameter read dominates, then saturates
        at the KV-bound ceiling."""
        flops = self.decode_flops_per_token * batch
        words = self.decode_step_bytes(batch) / M.BYTES_PER_WORD
        return flops / words

    # ---------------------------------------------------------- machines
    def workload(self, batch: int) -> M.Workload:
        """The serving Workload at batch B: inverse-AI anchoring off the
        DMM calibration (decode is MAC-dominated, so the per-PU speedup
        keeps the DMM value)."""
        return M.derived_workload(f"serve:{self.config}",
                                  self.decode_ai(batch))

    def traffic_bytes_per_s(self, batch: int, n_ap_pus: int) -> float:
        """Demand DRAM traffic at full utilization for the AP sized to
        ``n_ap_pus`` (shared by the same-performance SIMD pair)."""
        return M.traffic_bytes_per_s(self.decode_ai(batch), n_ap_pus)


@functools.lru_cache(maxsize=None)
def _params(config: str) -> tuple[int, int, int]:
    """(total, active, routed-expert) parameter counts of one config,
    from its parameter shapes; the rest of the total (shared experts,
    dense FFNs, attention, norms, embedding and head) is read once a
    decode step."""
    import jax.numpy as jnp
    import jax.tree_util as jtu
    from repro.configs import get_config
    from repro.launch import roofline as RF
    from repro.launch.steps import params_sds

    cfg = get_config(config)
    psds = params_sds(cfg, jnp.bfloat16)      # eval_shape only, no compile
    routed = sum(
        leaf.size for path, leaf in jtu.tree_leaves_with_path(psds)
        if "experts" in [getattr(k, "key", getattr(k, "name", str(k)))
                         for k in path])
    return (RF.count_params(psds), RF.count_active_params(cfg, psds),
            routed)


def serving_cost(config: str,
                 request: RequestShape = RequestShape()) -> ModelServingCost:
    """Build the analytic serving cost for one registered config."""
    from repro.configs import get_config
    cfg = get_config(config)
    n_total, n_active, routed = _params(config)
    moe = cfg.moe
    n_moe = cfg.n_layers - moe.first_dense if moe is not None else 0
    attn_decode, attn_prefill = mla_attn_flops(cfg)
    return ModelServingCost(
        config=config, request=request, n_params=float(n_total),
        n_active=float(n_active), kv_bytes_tok=kv_bytes_per_token(cfg),
        routed_params_layer=routed / n_moe if routed else 0.0,
        n_moe_layers=n_moe if routed else 0,
        n_experts=moe.n_routed if routed else 0,
        top_k=moe.top_k if routed else 0,
        attn_decode_flops_ctx=attn_decode,
        attn_prefill_flops_pair=attn_prefill)


__all__ = ["RequestShape", "ModelServingCost", "serving_cost",
           "kv_bytes_per_token", "experts_touched", "mla_attn_flops",
           "BYTES_PER_PARAM", "KV_BYTES_PER_EL"]
