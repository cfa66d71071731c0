"""One tiny model a block family, built once: what the tests that hold
every family to one rule (``test_parent_programs.py``, ``test_model_call.py``)
run on.  Widths are the smallest at which every branch of a family's layer
is taken: 4 heads, 8 experts with 3 a token of which 4 are held, latent
ranks 24 and 16, 4 state heads of 8 in 2 groups and a chunk of 8; ``zaya``:
4 heads over 2 of 8, both convolutions at 2 taps, a router of width 8 with
one pick of 4 experts; ``xing4``: joyai's sizes with every expert held on a
stream of 4 rows, 3 Sinkhorn iterations and YaRN of factor 4 over 8 positions."""

from scalerl_tpu.models.transformer import (
    RopeScaling,
    TransformerPolicy,
    block_spec,
    interval_specs,
    layer_specs,
    pattern_specs,
)

V = 53
_ROUTED = dict(num_experts=8, experts_per_token=3, expert_width=32)
_LATENT = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
_STATE = dict(ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_conv=4, ssm_chunk=8)


def _model(family, d_model=64, num_layers=2, stack=None, mtp_layers=0, **kw):
    spec = block_spec(family, **kw)
    return TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=d_model, num_heads=4, num_layers=num_layers,
        max_len=64, block=spec, layers=stack(spec) if stack else (), mtp_layers=mtp_layers,
    )


MODELS = {
    "gpt2": _model("gpt2"),
    "olmoe": _model("olmoe", head_dim=16, **_ROUTED),
    "longcat": _model(
        "longcat", **_ROUTED, **_LATENT, ffn_hidden=96, zero_experts=4, experts_held=4,
        routed_scaling=6.0, rope_theta=1e7,
    ),
    "joyai": _model(
        "joyai", num_layers=3, stack=lambda s: layer_specs(s, 3, 1), mtp_layers=1,
        norm_eps=1e-6, rope_theta=3.2e7, **_ROUTED, **_LATENT, norm_topk_prob=True,
        ffn_hidden=96, routed_scaling=2.5, scoring="sigmoid", shared_experts=1, experts_held=4,
    ),
    "nemotron": _model(
        "nemotron_h", d_model=32, num_layers=6, stack=lambda s: pattern_specs(s, "MEM*E-"),
        head_dim=8, norm_eps=1e-5, num_experts=8, experts_per_token=3, expert_width=16,
        norm_topk_prob=True, experts_held=4, routed_scaling=2.5, scoring="sigmoid",
        shared_experts=1, kv_heads=2, expert_act="relu2", shared_width=32, ffn_hidden=16,
        ssm_state=16, **_STATE,
    ),
    "qwen3next": _model(
        "qwen3_next", d_model=32, num_layers=5, stack=lambda s: interval_specs(s, 5, 4),
        head_dim=16, norm_eps=1e-6, rope_theta=1e7, num_experts=8, experts_per_token=3,
        expert_width=16, norm_topk_prob=True, experts_held=4, shared_experts=1,
        shared_width=16, kv_heads=2, ssm_state=8, rotary_dim=4, **_STATE,
    ),
    "zaya": _model(
        "zaya", d_model=32, num_layers=3, head_dim=8, norm_eps=1e-5, rope_theta=5e6,
        num_experts=4, experts_per_token=1, expert_width=16, kv_heads=2, rotary_dim=4,
        cca_time0=2, cca_time1=2, router_width=8,
    ),
    "xing4": _model(
        "xing4", num_layers=3, stack=lambda s: layer_specs(s, 3, 1), norm_eps=1e-6,
        **_ROUTED, **_LATENT, norm_topk_prob=True, ffn_hidden=96, routed_scaling=2.0,
        scoring="sigmoid", shared_experts=1, streams=4, hc_iters=3,
        rope_scaling=RopeScaling(4.0, 8, 32.0, 1.0, 1.0, 1.0),
    ),
}
