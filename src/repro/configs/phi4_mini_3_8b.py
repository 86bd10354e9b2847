"""phi4-mini-3.8b [dense]: RoPE + SwiGLU + GQA.

32L d_model=3072 24H (GQA kv=8, head_dim 128) d_ff=8192 vocab=200064,
tied embeddings.  Partial rotary: ``partial_rotary_factor`` 0.75, so the
first 96 of each head's 128 dims rotate (split-half) and the last 32 pass
through.  Departure: plain RoPE at theta 10000 over those dims, in place
of the published LongRoPE ``rope_scaling`` (its short/long factor lists
are not in this repository).
[arXiv:2503.01743; https://huggingface.co/microsoft/Phi-4-mini-instruct]
"""
from repro.models.config import LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="phi4-mini-3.8b",
    family="dense",
    n_layers=32,
    d_model=3072,
    n_heads=24,
    n_kv_heads=8,
    d_ff=8192,
    vocab=200064,
    pattern=(LayerSpec(mixer="attn", mlp="dense"),),
    norm="rmsnorm",
    act="swiglu",
    partial_rotary_factor=0.75,
    tie_embeddings=True,
    sub_quadratic=False,
)

SMOKE = ModelConfig(
    name="phi4-smoke",
    family="dense",
    n_layers=2,
    d_model=48,
    n_heads=6,          # 24H -> 6H keeps the non-16-divisible head count
    n_kv_heads=2,
    d_ff=96,
    vocab=512,
    pattern=(LayerSpec(mixer="attn", mlp="dense"),),
    norm="rmsnorm",
    act="swiglu",
    partial_rotary_factor=0.75,  # hd 8: the first 6 dims rotate
    scan_chunk=16,
)
