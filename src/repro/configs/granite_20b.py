"""granite-20b [dense] — IBM Granite-20B-Code, a GPTBigCode decoder.
[arXiv:2405.04324 Table 1; ibm-granite/granite-20b-code-base]
52L d_model=6144 48H over one KV head (multi-query) of 128, d_ff=24576
GELU MLP, a bias on every projection (the attention output's included),
pre-LayerNorm blocks with weight and bias (eps 1e-5), learned absolute
positions (8192 rows), vocab 49152, output head tied to the embedding.
The GELU is the exact (erf) form that GPTBigCode's ``"gelu"`` names.
"""
from repro.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-20b",
    family="dense",
    citation="arXiv:2405.04324",
    n_layers=52,
    d_model=6144,
    n_heads=48,
    n_kv_heads=1,
    d_ff=24576,
    vocab_size=49152,
    head_dim=128,
    qkv_bias=True,
    attn_out_bias=True,
    mlp_type="gelu",
    gelu_approximate=False,
    norm_kind="layernorm",
    norm_eps=1e-5,
    position_kind="learned",
    n_positions=8192,
    tie_embeddings=True,
)

REDUCED = CONFIG.with_(
    name="granite-20b-reduced",
    n_layers=2, d_model=384, n_heads=6, n_kv_heads=1, d_ff=1024,
    vocab_size=512, head_dim=64, n_positions=512,
)
