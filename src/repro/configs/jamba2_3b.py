"""jamba2-3b [ssm + attention] — AI21-Jamba2-3B: 28L d_model=2560, Mamba-1
mixers (d_state 16, d_conv 4, expand 2, dt_rank 160, RMSNorms on dt/B/C)
interleaved with full causal MQA attention (20 heads, 1 KV head, head dim
128, no RoPE) at every layer ``i % 14 == 7``; a SwiGLU FFN of 8192 after
every mixer (``num_experts`` 1: no MoE), vocab 65536, tied embeddings,
RMSNorm eps 1e-6.  Attribution keeps the SiLU gates' pre-activations exact
(``residual_policy="exact"``).
[https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json]

The config gives ``attn_layer_period`` 14 and ``attn_layer_offset`` 7 but
not the rule that places the layers; the ``transformers`` JambaConfig one
(``layers_block_type``: attention where ``i % period == offset``) is
assumed, which puts attention at layers 7 and 21.
"""
from repro.models.config import ModelConfig

FULL = ModelConfig(
    name="jamba2-3b",
    family="ssm",
    n_layers=28,
    d_model=2560,
    n_heads=20, n_kv=1, head_dim=128,
    d_ff=8192,
    vocab=65536,
    act="silu",
    rope=False,
    tie_embeddings=True,
    ssm_state=16,
    ssm_expand=2,                  # d_inner = 5120
    ssm_conv=4,
    dt_rank=160,
    ssm_inner_norm=True,
    attn_period=14,
    attn_offset=7,
    # the SiLU gates' backward keeps its pre-activations in full: an int8
    # residual would attribute below the precision the engine states
    residual_policy="exact",
)

#: one whole period (13 Mamba layers and the attention layer at index 7)
#: at CPU-test widths
SMOKE = FULL.with_(
    name="jamba2-3b-smoke",
    n_layers=14, d_model=32, n_heads=4, n_kv=1, head_dim=8, d_ff=64,
    vocab=256, ssm_state=4, dt_rank=4, ssm_chunk=8, dtype="float32",
)
