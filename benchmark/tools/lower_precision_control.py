"""Run one GPT-2 cell as ``benchmark/run.py`` does, with the plain reference
computed one precision BELOW the configuration's: the control a cell's
limits are set against (PERF.md, section 6).

    python benchmark/tools/lower_precision_control.py --operands float8_e4m3fn \\
        --workload gpt2m_closed_round --seed n --seconds 5 --trace 0

``gpt2-medium`` states float32 parameters at the TPU default precision,
where the MXU rounds matmul operands to bfloat16; the nearest precision
below is operands rounded to ``float8_e4m3fn``.  The control is
``reference/gpt2.py``'s forward written again with every matmul's two
operands rounded to ``--operands`` first (float32 accumulation, everything
else float32), put in that module's place for the cell's own ``check``:
the result line's ``correct`` must read false, and its ``notes`` say by
which of the cell's limits.  ``--operands bfloat16`` is the configuration's
own precision on the host: what operand rounding alone does to a probe.
The other arguments are ``run.py``'s.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import run  # noqa: E402  (benchmark/run.py)


def rounded_forward(gpt2, dtype):
    """``gpt2.forward`` with both operands of every matmul rounded to
    ``dtype``."""
    import jax
    import jax.numpy as jnp

    def low(x):
        return x.astype(dtype).astype(jnp.float32)

    def forward(params, tokens, n_head):
        p = params["params"]
        with jax.default_matmul_precision("highest"):
            B, T = tokens.shape
            x = (p["token_embed"]["embedding"][tokens] + p["pos_embed"][:T][None]).astype(jnp.float32)
            d = x.shape[-1]
            hd = d // n_head
            causal = jnp.tril(jnp.ones((T, T), bool))
            for i in range(sum(1 for k in p if k.startswith("block_"))):
                b = p[f"block_{i}"]
                h = gpt2._layer_norm(x, b["LayerNorm_0"]["scale"])
                q, k, v = (
                    part.reshape(B, T, n_head, hd)
                    for part in jnp.split(low(h) @ low(b["qkv"]["kernel"]), 3, axis=-1)
                )
                s = jnp.einsum("bqhd,bkhd->bhqk", low(q), low(k)) / jnp.sqrt(float(hd))
                a = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
                o = jnp.einsum("bhqk,bkhd->bqhd", low(a), low(v)).reshape(B, T, d)
                x = x + low(o) @ low(b["proj"]["kernel"])
                h = gpt2._layer_norm(x, b["LayerNorm_1"]["scale"])
                h = gpt2._gelu_new(low(h) @ low(b["mlp_in"]["kernel"]) + b["mlp_in"]["bias"])
                x = x + low(h) @ low(b["mlp_out"]["kernel"]) + b["mlp_out"]["bias"]
            x = low(gpt2._layer_norm(x, p["final_norm"]["scale"]))
            logits = x @ low(p["policy_head"]["kernel"]) + p["policy_head"]["bias"]
            values = (x @ low(p["value_head"]["kernel"]) + p["value_head"]["bias"])[..., 0]
        return logits, values

    return forward


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    at = argv.index("--operands")
    dtype = argv[at + 1]
    del argv[at : at + 2]
    load = harness.load_module

    def load_control(kind, name):
        module = load(kind, name)
        if (kind, name) == ("reference", "gpt2"):
            # token_logprobs looks ``forward`` up in its module: it follows
            module.forward = rounded_forward(module, dtype)
            print(f"[control] reference/gpt2.py's matmul operands rounded to {dtype}", flush=True)
        return module

    harness.load_module = load_control
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
