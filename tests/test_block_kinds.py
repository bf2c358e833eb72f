"""Block parts chosen by configuration: the norm (RMSNorm, or LayerNorm
with weight and bias), the positions (rotary, or a learned absolute
table), the attention output bias and the GELU form.

At their defaults they change nothing: tiny llama (smollm-360m) and
qwen2 (qwen1.5-4b) configurations build the same parameters and the same
prefill and decode logits, bit for bit, as before the fields existed
(digests pinned from that code on the CPU backend, jax 0.9.0).  Set for
GPTBigCode (granite-20b), the program's forward, chunked prefill and
paged decode agree with a plain float32 forward written out here."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, get_reduced_config
from repro.models import transformer as T

# sha256 (first 16 hex digits) of the parameters, a 13-token prefill
# chunk's logits and one paged decode step's logits and keys
PINNED = {
    "smollm-360m": {"params": "85129fa36fbd4392", "prefill": "59927c034a2c9e61",
                    "decode": "77017b17b40213ca"},
    "qwen1.5-4b": {"params": "3c5f33722c47e5bb", "prefill": "658180ffa4f00292",
                   "decode": "9d429f00cc8e956e"},
}


def _f32(arch):
    return get_reduced_config(arch).with_(param_dtype="float32",
                                          activation_dtype="float32")


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for name, a in arrays:
        a = np.asarray(a)
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("arch", sorted(PINNED))
def test_defaults_leave_rotary_blocks_unchanged(arch):
    cfg = _f32(arch)
    assert (cfg.norm_kind, cfg.position_kind, cfg.attn_out_bias,
            cfg.gelu_approximate) == ("rmsnorm", "rope", False, True)
    params = T.init_params(jax.random.PRNGKey(11), cfg, max_seq=64)
    got = {"params": _digest((jax.tree_util.keystr(p), x) for p, x in
                             jax.tree_util.tree_leaves_with_path(params))}
    cache = T.init_paged_cache(cfg, 9, 8)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                              cfg.vocab_size)
    bt = jnp.arange(1, 9, dtype=jnp.int32)[None]
    logits, _, cache = T.prefill_chunk(params, cfg, cache, toks, jnp.int32(13),
                                       jnp.int32(0), bt)
    got["prefill"] = _digest([("l", logits)])
    step, cache = T.decode_step(params, cfg, cache, toks[:, 13:14],
                                jnp.asarray([13], jnp.int32), block_tables=bt)
    got["decode"] = _digest([("l", step), ("k", cache["blocks"]["k"])])
    assert got == PINNED[arch]


def test_granite_configs_state_the_gpt_bigcode_block():
    for arch in ("granite-20b", "granite-34b"):
        for cfg in (get_config(arch), get_reduced_config(arch)):
            assert cfg.norm_kind == "layernorm" and cfg.norm_eps == 1e-5
            assert cfg.position_kind == "learned"
            assert cfg.qkv_bias and cfg.attn_out_bias and cfg.tie_embeddings
            assert cfg.mlp_type == "gelu" and not cfg.gelu_approximate
            assert cfg.n_kv_heads == 1
    assert get_config("granite-20b").n_positions == 8192
    assert get_config("granite-34b").n_layers == 88


# -- GPTBigCode against a plain forward -------------------------------------

def _bigcode():
    return get_reduced_config("granite-20b").with_(
        d_model=64, n_heads=4, head_dim=16, d_ff=128, vocab_size=96,
        n_positions=40, param_dtype="float32", activation_dtype="float32")


def _perturbed(cfg):
    """Initial weights with every bias, norm weight and bias moved off
    its zero / one start, so that each one counts."""
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])


def _plain_forward(p, cfg, toks):
    """LayerNorm -> biased q / k / v, one shared KV head, causal softmax
    at 1/sqrt(D) -> biased output projection -> residual; LayerNorm ->
    biased up-projection, exact GELU, biased down-projection -> residual;
    final LayerNorm; logits against the token embedding, which the
    program's head reads in bfloat16 (``layers.unembed``)."""
    def ln(x, w):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + cfg.norm_eps) * w["scale"] + w["bias"]

    H, D = cfg.n_heads, cfg.resolved_head_dim
    S = toks.shape[1]
    with jax.default_matmul_precision("highest"):
        x = p["embed"][toks] + p["pos_embed"][:S][None]
        for i in range(cfg.n_layers):
            w = jax.tree.map(lambda a: a[i], p["blocks"])
            a = w["attn"]
            h = ln(x, w["ln1"])
            q = (h @ a["w_q"] + a["b_q"]).reshape(1, S, H, D)
            k = h @ a["w_k"] + a["b_k"]                      # (1, S, D)
            v = h @ a["w_v"] + a["b_v"]
            s = jnp.einsum("bqhd,bkd->bhqk", q, k) / np.sqrt(D)
            s = jnp.where(np.tril(np.ones((S, S), bool)), s, -jnp.inf)
            o = jnp.einsum("bhqk,bkd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + o.reshape(1, S, H * D) @ a["w_o"] + a["b_o"]
            m = w["mlp"]
            u = ln(x, w["ln2"]) @ m["w_up"] + m["b_up"]
            x = x + jax.nn.gelu(u, approximate=False) @ m["w_down"] + m["b_down"]
        head = p["embed"].astype(jnp.bfloat16).astype(jnp.float32)
        return ln(x, p["final_norm"]) @ head.T


def test_gpt_bigcode_forward_matches_plain_forward():
    cfg = _bigcode()
    p = _perturbed(cfg)
    assert set(p) == {"embed", "pos_embed", "final_norm", "blocks"}
    assert set(p["blocks"]["attn"]) == {"w_q", "w_k", "w_v", "w_o", "b_q",
                                        "b_k", "b_v", "b_o"}
    assert set(p["final_norm"]) == {"scale", "bias"}
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, 24), 0, cfg.vocab_size)
    want = _plain_forward(p, cfg, toks)
    with jax.default_matmul_precision("highest"):
        got, _ = T.forward(p, cfg, {"tokens": toks}, mode="naive", remat=False)
    # float32 on both sides: summation order only
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    err = float(jnp.max(jnp.abs(got - want)))
    # the GELU form and the positions move the result well past that (the
    # norm is chosen where the parameters are made: LayerNorm's carry a
    # bias)
    for change in (dict(gelu_approximate=True), dict(position_kind="rope")):
        with jax.default_matmul_precision("highest"):
            other, _ = T.forward(p, cfg.with_(**change), {"tokens": toks},
                                 mode="naive", remat=False)
        assert float(jnp.max(jnp.abs(other - want))) > 10 * err, change


def test_gpt_bigcode_chunked_prefill_and_paged_decode_match_plain_forward():
    """Prompt chunks at offsets 0 and 8 (positions read from the table at
    ``pos_offset + i``), then decode steps at each row's ``pos``, through
    the paged pool: every position's logits are the plain forward's."""
    cfg = _bigcode()
    p = _perturbed(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 20), 0, cfg.vocab_size)
    want = _plain_forward(p, cfg, toks)[0]
    ps = 4
    cache = T.init_paged_cache(cfg, 8, ps)
    bt = jnp.asarray([[3, 1, 6, 2, 7, 5]], jnp.int32)     # 24 positions
    got = []
    with jax.default_matmul_precision("highest"):
        for off, n in ((0, 8), (8, 6)):
            chunk = jnp.zeros((1, 8), jnp.int32).at[0, :n].set(
                toks[0, off:off + n])
            logits, _, cache = T.prefill_chunk(p, cfg, cache, chunk,
                                               jnp.int32(n), jnp.int32(off), bt)
            got.extend(logits[0, :n])
        for pos in range(14, 20):
            step, cache = T.decode_step(p, cfg, cache, toks[:, pos:pos + 1],
                                        jnp.asarray([pos], jnp.int32),
                                        block_tables=bt)
            got.append(step[0, 0])
    np.testing.assert_allclose(jnp.stack(got), want, atol=1e-4, rtol=1e-4)
