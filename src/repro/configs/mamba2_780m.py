"""Mamba2-780m (state-spaces/mamba2-780m, config.json on the Hugging Face
hub; arXiv:2405.21060): attention-free SSD (state-space duality),
ssm_state=128, 48 layers, RMSNorm eps 1e-5, the residual stream carried
in float32, the head tied to the embedding.  The published table has
50,288 rows (50,277 tokens padded to a multiple of 16); 50,280 here, and
the benchmark's published configuration sets 50,288 through its
``program`` override."""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="mamba2-780m",
    family="ssm",
    num_layers=48,
    d_model=1536,
    num_heads=24,       # unused (attention-free); kept for config uniformity
    num_kv_heads=24,
    head_dim=64,
    d_ff=0,
    vocab_size=50280,
    ssm_state=128,
    ssm_conv=4,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_chunk=256,
    tie_embeddings=True,
    residual_in_fp32=True,
    rms_norm_eps=1e-5,
)
