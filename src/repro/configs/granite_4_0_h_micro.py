"""granite-4.0-h-micro — 40L d=2048, Mamba-2 and attention in one stack:
attention (32H GQA kv=8, head 64, no position embedding, softmax scale
0.015625) at layers i % 10 == 5, Mamba-2 (64 heads of 64, d_state 128, one
group, conv 4, chunk 256) everywhere else; every layer has its own SwiGLU
MLP (8192); embeddings x12, residual branches x0.22, logits /8, tied head,
vocab 100352.  The Mamba-2 heads take the model's head_dim, which the release
sets equal (mamba_d_head 64 = 2048 / 32).
[hf: ibm-granite/granite-4.0-h-micro config.json]"""
from repro.configs.base import ArchConfig, register
from repro.core.tensorized import TNNConfig
from repro.models.lm import LMConfig

GRANITE = dict(block="mamba2", mixer_period=10, mixer_offset=5,
               position_embedding="nope", tie_embeddings=True,
               embedding_multiplier=12.0, residual_multiplier=0.22,
               attention_multiplier=0.015625, logits_scaling=8.0)


def make_model(tnn=None):
    return LMConfig(
        name="granite-4.0-h-micro", num_layers=40, d_model=2048,
        num_heads=32, num_kv_heads=8, head_dim=64, d_ff=8192, vocab=100352,
        ssm_state=128, ssm_chunk=256, rope_theta=10000.0,
        norm_eps=1e-5, tnn=tnn or TNNConfig(), **GRANITE)


def make_smoke(tnn=None):
    """Four layers at small widths, attention third: the published
    period scaled to 4 (attention where i % 4 == 2)."""
    return LMConfig(
        name="granite-smoke", num_layers=4, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab=256, ssm_state=16,
        ssm_chunk=8, norm_eps=1e-5, remat=False,
        tnn=tnn or TNNConfig(), **{**GRANITE, "mixer_period": 4,
                                    "mixer_offset": 2})


CONFIG = register(ArchConfig(
    id="granite_4_0_h_micro", family="hybrid", model_kind="lm",
    make_model=make_model, make_smoke=make_smoke,
    notes="Mamba-2 state in 36 of 40 layers, KV cache in 4; long_500k "
          "skipped (its attention layers are full)",
))
