"""qwen3-moe-30b-a3b [moe] — 128 experts top-8 [hf:Qwen/Qwen3-30B-A3B]:
48L, d_model=2048, 32H (GQA kv=4, head_dim=128), expert d_ff=768,
vocab=151936, qk_norm, untied head, rms eps 1e-6, rope theta 1e6; every
layer is MoE (no shared expert), top-8 gates renormalised
(``norm_topk_prob``), as in the published ``config.json``.

Served with expert parallelism over ``SERVE_CHIPS`` chips: each chip holds
a contiguous quarter of the experts (``share_config``) and replicates
attention, router, embedding and head."""
from .base import ModelConfig, MoECfg

SERVE_CHIPS = 4


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-moe-30b-a3b", family="moe",
        n_layers=48, d_model=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        d_ff=768, vocab=151936,
        qk_norm=True, rope_theta=1_000_000.0, norm_eps=1e-6,
        tie_embeddings=False,
        ffn_pattern=("moe",),
        moe=MoECfg(n_experts=128, top_k=8, d_ff=768, norm_topk=True),
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-moe-smoke", family="moe",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=64, vocab=256,
        qk_norm=True,
        ffn_pattern=("moe",),
        moe=MoECfg(n_experts=8, top_k=2, d_ff=64),
        remat="none",
    )


def share_config(chip: int = 0) -> ModelConfig:
    """The published configuration as chip ``chip`` of ``SERVE_CHIPS``
    serves it: experts ``[32 chip, 32 chip + 32)`` of 128."""
    return config().expert_share(chip, SERVE_CHIPS)


def smoke_share_config(chip: int = 0) -> ModelConfig:
    """The smoke configuration's share: 2 of its 8 experts per chip."""
    return smoke_config().expert_share(chip, SERVE_CHIPS)
