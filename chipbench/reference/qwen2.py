"""Plain float32 reference of a Qwen2 decoder, written from the published
architecture and independent of the code under test.

Pre-norm decoder: RMSNorm -> GQA attention with QKV bias and half-rotation
RoPE -> residual -> RMSNorm -> SwiGLU MLP -> residual; final RMSNorm and a
head tied to the embedding. Every matmul runs in float32 at ``HIGHEST``
precision. The weight GEMMs (q, k, v, o, gate, up, down and the tied head)
see their operands rounded by the cell's numerics, ``rounding``:

* ``("fp32",)``: no rounding;
* ``("bf16",)``: both operands rounded to bfloat16;
* ``("bfp", b_m, g)``: block floating point, groups of ``g`` along the
  contraction axis sharing the exponent ``floor(log2 max|x|)``, signed
  mantissas of ``b_m`` magnitude bits rounded half to even (Mirage,
  arXiv:2311.17323, Sec. III-A).

Training rounds the two backward GEMMs of each weight GEMM the same way,
each along its own contraction axis (paper Eqs. 2-3), and keeps float32
master weights for AdamW (Eq. 4).

Attention's own two products (scores and values) see their operands
rounded as the configuration's ``attention_operands`` states, ``fp32`` (no
rounding) or ``bf16``: q, k, the unnormalised probabilities exp(s - max)
and v rounded to bfloat16, products summed in float32, and in training the
cotangent and the other operand of each backward product rounded the same
way. That is what a float32 dot at the TPU's default precision does. The
norms, the softmax's exponent and sum and the division by it are float32.

Weights are drawn from the seed by the configuration's initialisation
recipe: N(0, 1/d_in) for projections, N(0, 0.02^2) for the embedding, unit
norm scales and zero biases, one key split per layer.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# operand rounding
# --------------------------------------------------------------------------

def _bfp_last(x, b_m: int, g: int):
    """Round ``x`` to BFP(b_m, g) in groups along its last axis."""
    k = x.shape[-1]
    pad = (-k) % g
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x
    xg = xp.reshape(xp.shape[:-1] + (xp.shape[-1] // g, g))
    mx = jnp.max(jnp.abs(xg), axis=-1, keepdims=True)
    _, e = jnp.frexp(mx)              # mx = f * 2^e, f in [0.5, 1)
    e = jnp.where(mx > 0, e - 1, 0)   # floor(log2 mx)
    scale = jnp.ldexp(jnp.ones_like(mx), e - (b_m - 1))
    qmax = 2.0 ** b_m - 1
    q = jnp.clip(jnp.round(xg / scale), -qmax, qmax)
    out = (q * scale).reshape(xp.shape)
    return out[..., :k] if pad else out


def round_last(x, rounding):
    """``x`` rounded along its last (contraction) axis."""
    x = x.astype(jnp.float32)
    if rounding[0] == "fp32":
        return x
    if rounding[0] == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if rounding[0] == "bfp":
        return _bfp_last(x, rounding[1], rounding[2])
    raise ValueError(f"unknown rounding {rounding!r}")


def round_first(w, rounding):
    """``w`` (K, N) rounded along its first (contraction) axis."""
    return round_last(w.T, rounding).T


def _dot(a, b):
    return jnp.matmul(a, b, precision=HI, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def qmatmul(x, w, rounding):
    """``x @ w`` with both operands rounded along the contraction axis."""
    return _dot(round_last(x, rounding), round_first(w, rounding))


def _qmm_fwd(x, w, rounding):
    return qmatmul(x, w, rounding), (x, w)


def _qmm_bwd(rounding, res, gout):
    x, w = res
    gout = gout.astype(jnp.float32)
    dx = _dot(round_last(gout, rounding), round_first(w.T, rounding))
    x2 = x.reshape(-1, x.shape[-1])
    g2 = gout.reshape(-1, gout.shape[-1])
    dw = _dot(round_last(x2.T, rounding), round_first(g2, rounding))
    return dx.astype(x.dtype), dw.astype(w.dtype)


qmatmul.defvjp(_qmm_fwd, _qmm_bwd)


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def amm(eq: str, a, b, rounding):
    """``einsum(eq, a, b)`` with both operands rounded elementwise by
    ``rounding`` (``("fp32",)`` or ``("bf16",)``); in the backward pass the
    cotangent and the other operand are rounded the same way."""
    return _einsum(eq, round_last(a, rounding), round_last(b, rounding))


def _amm_fwd(eq, a, b, rounding):
    return amm(eq, a, b, rounding), (a, b)


def _amm_bwd(eq, rounding, res, gout):
    a, b = res
    _, pull = jax.vjp(lambda x, y: _einsum(eq, x, y),
                      round_last(a, rounding), round_last(b, rounding))
    return pull(round_last(gout.astype(jnp.float32), rounding))


amm.defvjp(_amm_fwd, _amm_bwd)


def rounding_of(policy: Dict) -> Tuple:
    """The operand rounding a configuration's ``policy`` entry states."""
    mode = policy["mode"]
    if mode in ("mirage", "mirage_fast"):
        return ("bfp", int(policy.get("b_m", 4)), int(policy.get("g", 16)))
    if mode in ("bf16", "fp32"):
        return (mode,)
    raise ValueError(f"the reference has no rounding for mode {mode!r}")


# --------------------------------------------------------------------------
# configuration and weights
# --------------------------------------------------------------------------

class Shape:
    """The sizes the reference reads from a configuration file."""

    def __init__(self, conf: Dict):
        self.d = int(conf["hidden_size"])
        self.ff = int(conf["intermediate_size"])
        self.h = int(conf["num_attention_heads"])
        self.kv = int(conf["num_key_value_heads"])
        self.n_layers = int(conf["num_hidden_layers"])
        self.vocab = int(conf["vocab_size"])
        self.hd = int(conf.get("head_dim") or self.d // self.h)
        self.eps = float(conf["rms_norm_eps"])
        self.theta = float(conf["rope_theta"])
        self.attn = (conf.get("attention_operands", "fp32"),)
        if self.attn[0] not in ("fp32", "bf16"):
            raise ValueError(f"attention_operands {self.attn[0]!r}")
        if not conf.get("tie_word_embeddings", False):
            raise ValueError("the reference implements the tied head only")


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _proj(key, d_in, d_out):
    wkey, _ = jax.random.split(key)
    return _normal(wkey, (d_in, d_out), 1.0 / math.sqrt(d_in))


def init_params(s: Shape, key) -> Dict:
    keys = jax.random.split(key, 8)

    def layer(k):
        ks = jax.random.split(k, 4)
        ka = jax.random.split(ks[0], 5)
        km = jax.random.split(ks[1], 3)
        hq, hk = s.h * s.hd, s.kv * s.hd
        return {
            "ln1": {"scale": jnp.ones((s.d,), jnp.float32)},
            "attn": {
                "q": {"w": _proj(ka[0], s.d, hq), "b": jnp.zeros((hq,))},
                "k": {"w": _proj(ka[1], s.d, hk), "b": jnp.zeros((hk,))},
                "v": {"w": _proj(ka[2], s.d, hk), "b": jnp.zeros((hk,))},
                "o": {"w": _proj(ka[3], hq, s.d)},
            },
            "ln2": {"scale": jnp.ones((s.d,), jnp.float32)},
            "mlp": {"gate": {"w": _proj(km[0], s.d, s.ff)},
                    "up": {"w": _proj(km[1], s.d, s.ff)},
                    "down": {"w": _proj(km[2], s.ff, s.d)}},
        }

    return {
        "embed": {"emb": _normal(keys[1], (s.vocab, s.d), 0.02)},
        "layers": jax.vmap(layer)(jax.random.split(keys[0], s.n_layers)),
        "final_norm": {"scale": jnp.ones((s.d,), jnp.float32)},
    }


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """Half-rotation RoPE. x: (B, L, H, D), pos: (L,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv          # (L, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s: Shape, rounding, h, lp):
    B, L, _ = h.shape
    pos = jnp.arange(L)
    a = lp["attn"]
    x = _rms(h, lp["ln1"]["scale"], s.eps)
    q = (qmatmul(x, a["q"]["w"], rounding) + a["q"]["b"]).reshape(
        B, L, s.h, s.hd)
    k = (qmatmul(x, a["k"]["w"], rounding) + a["k"]["b"]).reshape(
        B, L, s.kv, s.hd)
    v = (qmatmul(x, a["v"]["w"], rounding) + a["v"]["b"]).reshape(
        B, L, s.kv, s.hd)
    q, k = _rope(q, pos, s.theta), _rope(k, pos, s.theta)
    rep = s.h // s.kv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = amm("bqhd,bkhd->bhqk", q, k, s.attn) / math.sqrt(s.hd)
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    e = jnp.exp(sc - jax.lax.stop_gradient(jnp.max(sc, -1, keepdims=True)))
    den = jnp.sum(e, axis=-1)                              # (B, H, L)
    ctx = amm("bhqk,bkhd->bqhd", e, v, s.attn) / \
        jnp.moveaxis(den, 1, 2)[..., None]
    ctx = ctx.reshape(B, L, s.h * s.hd)
    h = h + qmatmul(ctx, a["o"]["w"], rounding)
    m = lp["mlp"]
    x = _rms(h, lp["ln2"]["scale"], s.eps)
    u = jax.nn.silu(qmatmul(x, m["gate"]["w"], rounding)) * \
        qmatmul(x, m["up"]["w"], rounding)
    return h + qmatmul(u, m["down"]["w"], rounding)


def hidden(s: Shape, rounding, params, tokens):
    """Final-normed hidden states (B, L, d); layers rematerialised."""
    h = jnp.take(params["embed"]["emb"], tokens, axis=0)
    body = jax.checkpoint(lambda hh, lp: (_layer(s, rounding, hh, lp), None))
    h, _ = jax.lax.scan(body, h, params["layers"])
    return _rms(h, params["final_norm"]["scale"], s.eps)


def head(rounding, params, x):
    return qmatmul(x, params["embed"]["emb"].T, rounding)


def loss(s: Shape, rounding, params, tokens, labels, chunk: int = 512):
    """Mean next-token cross-entropy, the head applied ``chunk`` rows at a
    time so that (tokens, vocab) logits never exist whole."""
    x = hidden(s, rounding, params, tokens)
    x = x.reshape(-1, s.d)
    y = labels.reshape(-1)
    n = x.shape[0]
    chunk = min(chunk, n)
    assert n % chunk == 0, (n, chunk)

    def body(acc, xs):
        xc, yc = xs
        lp = jax.nn.log_softmax(head(rounding, params, xc), axis=-1)
        return acc - jnp.sum(jnp.take_along_axis(lp, yc[:, None], -1)), None

    body = jax.checkpoint(body)
    tot, _ = jax.lax.scan(body, jnp.zeros(()), (
        x.reshape(n // chunk, chunk, s.d), y.reshape(n // chunk, chunk)))
    return tot / n


# --------------------------------------------------------------------------
# training: three AdamW steps with global-norm clipping
# --------------------------------------------------------------------------

def leaf_norms(tree) -> Dict[str, jax.Array]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in flat}


def train_readings(conf: Dict, policy: Dict, key, batches: Sequence[Dict],
                   lr: float, clip: float, b1: float = 0.9,
                   b2: float = 0.999, eps: float = 1e-8,
                   ce_chunk: int = 512, grad: Dict = None,
                   keep_grad: bool = False) -> Dict:
    """Run ``len(batches)`` AdamW steps from the seed's weights. Returns the
    loss of each step, each leaf's norm of the first (clipped) gradient and
    of the parameters' change over all the steps. Given ``grad`` (a first
    gradient by leaf name, as host arrays), also each leaf's norm of its
    difference from the reference's first gradient. With ``keep_grad``,
    also that first gradient by leaf name as host arrays (``grad_arrays``),
    to be given to another run as its ``grad``."""
    s = Shape(conf)
    rounding = rounding_of(policy)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, c, tokens, labels):
        l, g = jax.value_and_grad(
            lambda p: loss(s, rounding, p, tokens, labels, ce_chunk))(params)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9)), g)
        c = c + 1.0
        m = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x,
                                   v, g)
        mh, vh = 1.0 / (1.0 - b1 ** c), 1.0 / (1.0 - b2 ** c)
        params = jax.tree_util.tree_map(
            lambda p, a, b: p - lr * (a * mh) / (jnp.sqrt(b * vh) + eps),
            params, m, v)
        return params, m, v, c, l, g

    init = jax.jit(functools.partial(init_params, s))
    params = init(key)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    c = jnp.zeros((), jnp.float32)
    losses, g1 = [], None
    for i, b in enumerate(batches):
        params, m, v, c, l, g = step(params, m, v, c, jnp.asarray(
            b["tokens"]), jnp.asarray(b["labels"]))
        losses.append(float(l))
        if i == 0:
            g1 = g
        del g
    del m, v
    gnorms = {k: float(x) for k, x in jax.jit(leaf_norms)(g1).items()}
    arrays = None
    if keep_grad:
        flat, _ = jax.tree_util.tree_flatten_with_path(g1)
        arrays = {"/".join(str(getattr(q, "key", q)) for q in path):
                  jax.device_get(x) for path, x in flat}
    diff = None
    if grad is not None:
        # each leaf's norm of (program's gradient - reference's), one leaf
        # on the device at a time
        flat, _ = jax.tree_util.tree_flatten_with_path(g1)
        sub = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        diff = {}
        for path, x in flat:
            k = "/".join(str(getattr(q, "key", q)) for q in path)
            diff[k] = float(sub(jnp.asarray(grad[k]), x))
    del g1
    # the starting weights are drawn again rather than kept alongside
    change = jax.jit(lambda a, k: leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, a, init_params(s, k))))(params, key)
    out = {"losses": losses, "grad": gnorms, "grad_diff": diff,
           "change": {k: float(x) for k, x in change.items()}}
    if keep_grad:
        out["grad_arrays"] = arrays
    return out
