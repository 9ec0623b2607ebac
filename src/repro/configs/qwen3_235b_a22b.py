"""qwen3-235b-a22b [paper model]: 94L d_model=4096 64H (GQA kv=4, head 128,
q/k RMS norm) every layer MoE: 128 experts top-8, softmax over all 128 with
the top-8 weights renormalised, expert d_ff=1536, no shared expert;
vocab=151936 untied, RoPE theta 1e6.  Paper Table 3 evaluation model.
[hf Qwen/Qwen3-235B-A22B config.json; arXiv:2505.09388]
"""
from repro.configs.base import ModelConfig, MoEArch, register


@register("qwen3-235b-a22b")
def qwen3_235b_a22b() -> ModelConfig:
    return ModelConfig(
        name="qwen3-235b-a22b",
        family="moe",
        num_layers=94,
        d_model=4096,
        vocab_size=151_936,
        num_heads=64,
        num_kv_heads=4,
        head_dim=128,
        qk_norm=True,
        rope_theta=1e6,
        moe=MoEArch(num_experts=128, top_k=8, d_ff=1536, score_fn="softmax",
                    norm_topk_prob=True, n_slot=2),
        shape_skips=("long_500k",),
        source="https://huggingface.co/Qwen/Qwen3-235B-A22B/blob/main/"
               "config.json (arXiv:2505.09388)",
    )
