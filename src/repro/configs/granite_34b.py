"""granite-34b [dense] — IBM Granite-34B-Code, a GPTBigCode decoder
depth-upscaled from the 20B: the same block over 88 layers.
[arXiv:2405.04324 Table 1 and §3; ibm-granite/granite-34b-code-base]
88L d_model=6144 48H over one KV head (multi-query) of 128, d_ff=24576
GELU MLP (exact erf form), a bias on every projection, pre-LayerNorm
blocks with weight and bias (eps 1e-5), learned absolute positions (8192
rows), vocab 49152, output head tied to the embedding.
"""
from repro.configs.granite_20b import CONFIG as GRANITE_20B

CONFIG = GRANITE_20B.with_(name="granite-34b", n_layers=88)

REDUCED = CONFIG.with_(
    name="granite-34b-reduced",
    n_layers=2, d_model=384, n_heads=6, n_kv_heads=1, d_ff=1024,
    vocab_size=512, head_dim=64, n_positions=512,
)
