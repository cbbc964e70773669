"""nemotron3-nano-30b-a3b — hybrid Mamba-2 / attention / MoE, 52 layers.
[hf: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json; Nemotron-H,
arXiv:2504.03624]

Every layer is one pre-RMSNorm residual branch with one mixer, as the
published ``hybrid_override_pattern`` lists them: M Mamba-2 (64 heads of
64, state 128, 8 B/C groups, the gated norm over 512-lane groups), *
GQA attention (32 query and 2 KV heads of 128, no positional encoding),
E an expert layer (sigmoid router over 128 relu^2 experts of 1856, top-6,
gate weights normalized and times 2.5, one shared relu^2 expert of 3712).
The published pattern has no period, so the whole model is one block of
52 layers; a configuration cut to its first 28 layers repeats
``MEMEM*E`` four times.
"""
from repro.config import ModelConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def config() -> ModelConfig:
    return ModelConfig(
        name="nemotron3-nano-30b-a3b",
        family="hybrid",
        n_layers=len(PATTERN),
        layer_pattern=PATTERN,
        d_model=2688,
        n_heads=32,
        n_kv_heads=2,
        head_dim=128,
        use_rope=False,
        d_ff=1856,
        vocab_size=131072,
        n_experts=128,
        top_k=6,
        routed_scaling=2.5,
        shared_expert_ff=3712,
        ssm_state=128,
        ssm_heads=64,
        ssm_headdim=64,
        ssm_groups=8,
        ssm_norm_group=512,
        ssm_chunk=128,
        norm_eps=1e-5,
    )
