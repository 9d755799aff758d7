"""mamba2-2.7b — SSD (state-space duality), attention-free. [arXiv:2405.21060; hf]

Checked against state-spaces/mamba2-2.7b ``config.json`` (d_model 2560,
n_layer 64, vocab_size 50277 padded to a multiple of 16, tied embeddings)
and mamba_ssm's Mamba2 and MixerModel defaults it leaves to the code
(d_state 128, d_conv 4, expand 2, headdim 64, ngroups 1, chunk_size 256,
norm_epsilon 1e-5), as held in ``bench/configs/mamba2-2.7b-s8.json``.
"""
from repro.configs import ModelConfig

CONFIG = ModelConfig(
    name="mamba2-2.7b",
    family="ssm",
    num_layers=64,
    d_model=2560,
    vocab_size=50_288,      # 50,277 padded to a multiple of 16
    d_ff=0,                 # attention-free, no FFN blocks: mamba2 mixer only
    ssm_state=128,
    ssm_expand=2,           # d_inner = 5120
    ssm_head_dim=64,        # 80 SSD heads
    ssm_ngroups=1,
    ssm_conv=4,
    ssm_chunk=256,
    norm="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=True,
    source="arXiv:2405.21060; hf",
)
