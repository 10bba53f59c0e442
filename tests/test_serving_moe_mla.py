"""Serving cost by mechanism: routed experts at batch B and latent attention.

Hand counts at DeepSeek-V2-Lite's published widths (arXiv:2405.04434:
27 layers, d 2048, 16 heads, MLA kv_lora 512 / rope 64 / nope 128 / v
128, first layer dense at 10944, then 64 routed experts of 1408, top-6,
2 shared, vocabulary 102400), from parameter shapes only.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs
from repro.models import moe
from repro.serving import RequestShape, serving_cost
from repro.serving.cost import experts_touched

LITE = "deepseek-v2-lite-16b"
REQ = RequestShape(1024, 8192)

# published widths, written out by hand
D, H, V, L, FIRST = 2048, 16, 102400, 27, 1
KV, ROPE, NOPE, VD = 512, 64, 128, 128
E, K, SHARED, DE, DFF = 64, 6, 2, 1408, 10944
N_MOE = L - FIRST
ROUTED_LAYER = 3 * D * DE * E
ATTN = (D * H * (NOPE + ROPE) + D * (KV + ROPE) + KV + KV * H * (NOPE + VD)
        + H * VD * D)
TOTAL = (2 * V * D + D + L * (ATTN + 2 * D) + FIRST * 3 * D * DFF
         + N_MOE * (D * E + 3 * D * DE * SHARED + ROUTED_LAYER))


def _hand_weight_bytes(b: int) -> float:
    touched = E * (1.0 - (1.0 - K / E) ** b)
    return 2.0 * (TOTAL - N_MOE * ROUTED_LAYER
                  + N_MOE * ROUTED_LAYER * touched / E)


@pytest.fixture(scope="module")
def lite():
    return serving_cost(LITE, REQ)


def test_parameter_split_matches_the_published_widths(lite):
    assert lite.n_params == TOTAL
    assert abs(TOTAL / 15.7e9 - 1.0) < 0.01          # "15.7B" published
    assert lite.routed_params_layer == ROUTED_LAYER
    assert (lite.n_moe_layers, lite.n_experts, lite.top_k) == (N_MOE, E, K)
    # what is not routed: shared experts, the dense FFN, attention, norms,
    # router, embedding and head
    assert lite.n_params - N_MOE * lite.routed_params_layer \
        == FIRST * 3 * D * DFF + N_MOE * (3 * D * DE * SHARED + D * E) \
        + 2 * V * D + D + L * (ATTN + 2 * D)


@pytest.mark.parametrize("b", [1, 2, 8, 32, 64])
def test_weight_bytes_per_step_is_the_hand_count(lite, b):
    assert lite.weight_bytes_per_step(b) == pytest.approx(
        _hand_weight_bytes(b), rel=1e-12)
    assert lite.experts_touched(b) == pytest.approx(
        E * (1.0 - (1.0 - K / E) ** b), rel=1e-12)
    assert lite.kv_bytes_per_step(b) == b * L * (KV + ROPE) * 2.0 \
        * lite.mean_context
    assert lite.decode_step_bytes(b) == lite.weight_bytes_per_step(b) \
        + lite.kv_bytes_per_step(b)


def test_experts_at_batch_rise_and_saturate(lite):
    bs = [1, 2, 4, 8, 16, 32, 64, 128, 1024]
    touched = [lite.experts_touched(b) for b in bs]
    wb = [lite.weight_bytes_per_step(b) for b in bs]
    assert touched[0] == K
    assert all(y > x for x, y in zip(touched, touched[1:]))
    assert all(y > x for x, y in zip(wb, wb[1:]))
    assert touched[-1] == pytest.approx(E) and touched[-1] <= E
    assert wb[-1] == pytest.approx(2.0 * TOTAL) and wb[-1] <= 2.0 * TOTAL
    # experts touched: 20.8 at B = 4, 34.9 at 8, 61.3 at 32
    assert [round(lite.experts_touched(b), 1) for b in (4, 8, 32)] \
        == [20.8, 34.9, 61.3]
    # B = 32 streams 5.7x the batch-1 (active-parameter) bytes
    assert lite.weight_bytes_per_step(32) / lite.param_bytes \
        == pytest.approx(5.67, abs=0.01)


def test_latent_attention_flops(lite):
    ctx = 1024 + 8192 / 2
    per_ctx = 2 * H * (KV + ROPE) + 2 * H * KV
    assert per_ctx == 34816
    assert lite.attn_flops_per_token == L * per_ctx * ctx
    pair = 2 * H * (NOPE + ROPE) + 2 * H * VD
    assert lite.attn_prefill_flops == L * pair * 1024 * 1025 / 2
    assert lite.decode_flops_per_token == 2.0 * lite.n_active \
        + lite.attn_flops_per_token
    assert lite.prefill_flops == 2.0 * lite.n_active * 1024 \
        + lite.attn_prefill_flops
    assert lite.request_flops == lite.prefill_flops \
        + 8192 * lite.decode_flops_per_token
    # decode AI(32) about halves against the active-parameter rule
    old = 2.0 * lite.n_active * 32 / (
        (lite.param_bytes + lite.kv_bytes_per_step(32)) / 4.0)
    assert old == pytest.approx(65.4, abs=0.1)
    assert lite.decode_ai(32) == pytest.approx(36.8, abs=0.1)


@pytest.mark.parametrize("name", list_configs())
def test_batch_one_is_the_active_parameter_stream(name):
    cost = serving_cost(name)
    assert cost.weight_bytes_per_step(1) == cost.param_bytes
    assert cost.param_bytes == 2.0 * cost.n_active


@pytest.mark.parametrize("name", [n for n in list_configs()
                                  if get_config(n).moe is None])
def test_models_without_experts_or_mla_price_as_before(name):
    cost = serving_cost(name)
    cfg = get_config(name)
    p = cost.request.prompt_tokens
    for b in (1, 2, 8, 32, 64):
        assert cost.weight_bytes_per_step(b) == 2.0 * cost.n_active
        assert cost.decode_step_bytes(b) == 2.0 * cost.n_active \
            + b * cost.kv_bytes_tok * cost.mean_context
    if cfg.mla is None:
        assert cost.prefill_flops == 2.0 * cost.n_active * p
        assert cost.decode_flops_per_token == 2.0 * cost.n_active


def test_a_fractional_batch_is_refused(lite):
    with pytest.raises(ValueError):
        lite.weight_bytes_per_step(2.5)
    with pytest.raises(ValueError):
        lite.weight_bytes_per_step(0)


@pytest.mark.parametrize("b", [4, 16, 32])
def test_uniform_routing_matches_the_repo_router(b):
    """Distinct experts that B tokens route to through the router of
    `moe.moe_init` and the softmax top-k of `moe.moe_ffn`, at the
    published 64 experts top-6 with a small random model width."""
    cfg = dataclasses.replace(get_config(LITE), d_model=256)
    params = moe.moe_init(jax.random.PRNGKey(16), cfg)
    x = jax.random.normal(jax.random.PRNGKey(b), (4000, b, cfg.d_model))
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    _, ids = jax.lax.top_k(probs, cfg.moe.top_k)
    hit = jnp.zeros((x.shape[0], cfg.moe.n_routed), bool)
    hit = hit.at[jnp.arange(x.shape[0])[:, None, None], ids].set(True)
    mean = float(np.asarray(hit.sum(-1)).mean())
    want = experts_touched(cfg.moe.n_routed, cfg.moe.top_k, b)
    assert mean == pytest.approx(want, rel=0.03)


def test_the_cosim_counts_each_mechanism_and_keeps_every_round():
    from repro import obs
    from repro.serving import ServingScenario, TrafficSpec, run_serving_cosim
    sc = ServingScenario(
        config=LITE, traffic=TrafficSpec(shape="bursty", horizon_s=60.0),
        request=REQ, load=0.7, grid_n=8, n_rounds=2, pad_quantum=16)
    with obs.scoped():
        obs.reset()
        reps = run_serving_cosim(sc)
        hist = obs.snapshot()["histograms"]
        obs.reset()
    n = 2 * sc.traffic.n_intervals                  # two machines
    touched = hist["serving/experts_touched"]
    assert touched["count"] == n
    assert K <= touched["min"] <= touched["max"] <= E
    assert hist["serving/weight_bytes_per_step"]["count"] == n
    assert hist["serving/kv_bytes_per_step"]["count"] == n
    cost = serving_cost(LITE, REQ)
    assert hist["serving/attn_flops_per_token"]["max"] \
        == pytest.approx(cost.attn_flops_per_token)
    for rep in reps.values():
        assert len(rep.rounds) == sc.n_rounds
        last = rep.rounds[-1]
        np.testing.assert_array_equal(last.peak_C, rep.stack.peak_C)
        np.testing.assert_array_equal(last.throttle, rep.stack.throttle)
        np.testing.assert_array_equal(last.latency_s, rep.latency_s)
