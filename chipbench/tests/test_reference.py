"""The reference's attention products round as the configuration states."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import qwen2 as reference

EQ = "bqhd,bkhd->bhqk"


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _operands():
    ka, kb, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    a = jax.random.normal(ka, (2, 8, 3, 16), jnp.float32)
    b = jax.random.normal(kb, (2, 8, 3, 16), jnp.float32)
    g = jax.random.normal(kg, (2, 3, 8, 8), jnp.float32)
    return a, b, g


def _plain(a, b):
    return jnp.einsum(EQ, a, b, precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("mode", ["fp32", "bf16"])
def test_forward_rounds_both_operands(mode):
    a, b, _ = _operands()
    r = _bf16 if mode == "bf16" else (lambda x: x)
    got = reference.amm(EQ, a, b, (mode,))
    np.testing.assert_allclose(got, _plain(r(a), r(b)), rtol=1e-6,
                               atol=1e-6)


def test_backward_rounds_the_cotangent_and_the_other_operand():
    a, b, g = _operands()
    _, pull = jax.vjp(lambda x, y: reference.amm(EQ, x, y, ("bf16",)), a, b)
    da, db = pull(g)
    _, want = jax.vjp(_plain, _bf16(a), _bf16(b))
    wa, wb = want(_bf16(g))
    np.testing.assert_allclose(da, wa, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(db, wb, rtol=1e-6, atol=1e-6)
    # and it is not the unrounded gradient
    _, exact = jax.vjp(_plain, a, b)
    assert float(jnp.max(jnp.abs(da - exact(g)[0]))) > 1e-4


def test_float32_attention_is_softmax_attention():
    """With no rounding, the layer's attention equals softmax(q k^T) v."""
    conf = {"hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_hidden_layers": 1, "vocab_size": 32,
            "rms_norm_eps": 1e-6, "rope_theta": 1e4,
            "tie_word_embeddings": True, "attention_operands": "fp32"}
    s = reference.Shape(conf)
    params = reference.init_params(s, jax.random.PRNGKey(0))
    lp = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
    lp["attn"]["o"]["w"] = jnp.eye(64)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
    mlp0 = jax.tree_util.tree_map(jnp.zeros_like, lp["mlp"])
    got = reference._layer(s, ("fp32",), h, dict(lp, mlp=mlp0)) - h

    a = lp["attn"]
    x = reference._rms(h, lp["ln1"]["scale"], s.eps)
    pos = jnp.arange(8)
    q = reference._rope((x @ a["q"]["w"]).reshape(2, 8, 4, 16), pos, 1e4)
    k = reference._rope((x @ a["k"]["w"]).reshape(2, 8, 2, 16), pos, 1e4)
    v = (x @ a["v"]["w"]).reshape(2, 8, 2, 16)
    k, v = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    np.testing.assert_allclose(got, want.reshape(2, 8, 64), rtol=1e-4,
                               atol=1e-5)
