from scalerl_tpu.ops.losses import (  # noqa: F401
    baseline_loss,
    c51_loss,
    categorical_projection,
    categorical_q_values,
    double_dqn_targets,
    dqn_loss,
    entropy_loss,
    make_support,
    policy_gradient_loss,
)
from scalerl_tpu.ops.pallas_attention import flash_attention  # noqa: F401
from scalerl_tpu.ops.pallas_paged_attention import (  # noqa: F401
    make_paged_attn_fn,
    paged_attention_reference,
    paged_decode_attention,
    paged_decode_latent,
    paged_latent_attention_reference,
    resolve_paged_attn,
)
from scalerl_tpu.ops.ring_attention import (  # noqa: F401
    full_attention,
    make_ring_attention_fn,
    ring_attention,
)
from scalerl_tpu.ops.returns import (  # noqa: F401
    discounted_returns,
    gae_advantages,
    n_step_returns,
)
from scalerl_tpu.ops.vtrace import (  # noqa: F401
    VTraceOutput,
    vtrace_from_importance_weights,
    vtrace_from_logits,
)
