"""NVIDIA-Nemotron-3-Nano-30B-A3B — Mamba-2, GQA and expert layers in one
52-layer pattern; 128 relu² experts (top 6, sigmoid router with correction
bias, routed scale 2.5) plus a shared expert; no position embedding in the
attention layers. [https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16]"""
from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="nemotron-3-nano-30b-a3b", family="hybrid_moe",
    n_layers=52, d_model=2688, n_heads=32, n_kv_heads=2, head_dim=128,
    d_ff=0, vocab=131072,
    layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    rope="none", norm="rmsnorm", norm_eps=1e-5, tie_embeddings=False,
    n_experts=128, n_router_experts=128, top_k=6, moe_d_ff=1856,
    shared_expert_d_ff=3712, routed_scale=2.5,
    ssm_state=128, ssm_head_dim=64, ssm_n_heads=64, ssm_groups=8,
    ssm_chunk=128,
    source="https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16",
))
