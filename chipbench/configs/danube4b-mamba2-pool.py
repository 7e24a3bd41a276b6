"""Plain reference for the danube4b-mamba2-pool configuration.

h2o-danube-3-4b (dense decoder, grouped-query attention, RoPE, SwiGLU,
RMSNorm) and mamba2-780m (Mamba2 blocks: input projection, depthwise causal
conv, selective state-space recurrence, gated RMSNorm) written out in
straightforward `jax.numpy` at float32 with `precision="highest"`. It
imports nothing of the program under test.

It also makes the weights: one jitted call per member draws every leaf
from the run's seed, in the dtype the configuration serves (bfloat16), in
the layout the serving engine takes (leaves stacked over layers). The
reference reads those same bfloat16 values, cast to float32.

`precision="fp8"` is the control: every matrix-multiply operand (weights,
activations, the attention's q, k and v) rounded to float8 e4m3, the
nearest precision below the configuration's bfloat16.

The work functions give the FLOPs and bytes one token needs, for the
per-layer metrics `decode_roofline` and `served_mfu`; `tiny_arch` at the end
gives each member's sizes for the CPU tests.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-6
CONV_WIDTH = 4
HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------- weights
def d_inner(a: Dict) -> int:
    return a["ssm_expand"] * a["d_model"]


def ssm_heads(a: Dict) -> int:
    return d_inner(a) // a["ssm_head_dim"]


def param_tree(a: Dict) -> Dict:
    """The serving layout: {path: (shape, init, std)}, leaves stacked over
    layers. Normal draws use std 0.02, output projections 0.02/sqrt(2L)."""
    d, v, nl = a["d_model"], a["vocab"], a["n_layers"]
    std, std_o = 0.02, 0.02 / math.sqrt(2 * nl)
    t = {"embed": {"tok": ((v, d), "normal", std)},
         "lnf": {"scale": ((d,), "ones", 0.0)}}
    if not a.get("tie_embeddings"):
        t["embed"]["unembed"] = ((d, v), "normal", std)
    if a["family"] == "dense":
        h, kv, hd, f = a["n_heads"], a["n_kv_heads"], a["head_dim"], a["d_ff"]
        layer = {"ln1": {"scale": ((d,), "ones", 0.0)},
                 "attn": {"wq": ((d, h, hd), "normal", std),
                          "wk": ((d, kv, hd), "normal", std),
                          "wv": ((d, kv, hd), "normal", std),
                          "wo": ((h, hd, d), "normal", std_o)},
                 "ln2": {"scale": ((d,), "ones", 0.0)},
                 "mlp": {"wi_gate": ((d, f), "normal", std),
                         "wi_up": ((d, f), "normal", std),
                         "wo": ((f, d), "normal", std_o)}}
    elif a["family"] == "ssm":
        di, n, hh = d_inner(a), a["ssm_state"], ssm_heads(a)
        conv_ch = di + 2 * n
        layer = {"ln1": {"scale": ((d,), "ones", 0.0)},
                 "ssm": {"w_in": ((d, 2 * di + 2 * n + hh), "normal", std),
                         "conv_w": ((CONV_WIDTH, conv_ch), "normal", std),
                         "conv_b": ((conv_ch,), "zeros", 0.0),
                         "a_log": ((hh,), "ones", 0.0),
                         "d_skip": ((hh,), "ones", 0.0),
                         "dt_bias": ((hh,), "zeros", 0.0),
                         "norm": ((di,), "ones", 0.0),
                         "w_out": ((di, d), "normal", std_o)}}
    else:
        raise ValueError(f"no reference for family {a['family']!r}")
    t["layers"] = jax.tree.map(lambda s: ((nl,) + s[0], s[1], s[2]), layer,
                               is_leaf=lambda s: isinstance(s, tuple))
    return t


def _is_spec(s) -> bool:
    return isinstance(s, tuple) and len(s) == 3 and isinstance(s[0], tuple)


def init_params(a: Dict, key, dtype=jnp.bfloat16):
    """Every leaf of ``param_tree(a)`` in one jitted call, on the device."""
    tree = param_tree(a)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for (shape, init, std), k in zip(leaves, keys):
            if init == "ones":
                out.append(jnp.ones(shape, dtype))
            elif init == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * std).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return make(key)


def param_count(a: Dict) -> int:
    return sum(math.prod(s[0]) for s in
               jax.tree.leaves(param_tree(a), is_leaf=_is_spec))


# ----------------------------------------------------------- forward
def _q(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=HIGHEST)


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) \
        * scale


def _rope(x, pos, theta: float):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) * 2.0
                           / x.shape[-1]))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _dense_layer(a: Dict, p, x, precision: str):
    b, s, _ = x.shape
    h, kv, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    pos = jnp.arange(s)
    y = _rms(x, p["ln1"]["scale"])
    q = _rope(_mm("bsd,dhk->bshk", y, p["attn"]["wq"], precision), pos,
              a["rope_theta"])
    k = _rope(_mm("bsd,dhk->bshk", y, p["attn"]["wk"], precision), pos,
              a["rope_theta"])
    v = _mm("bsd,dhk->bshk", y, p["attn"]["wv"], precision)
    group = jnp.arange(h) // (h // kv)             # query head -> kv head
    k, v = k[:, :, group], v[:, :, group]
    sc = _mm("bshk,bthk->bhst", q, k, precision) / math.sqrt(hd)
    qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = ki <= qi
    if a.get("sliding_window"):
        mask &= ki > qi - a["sliding_window"]
    sc = jnp.where(mask, sc, -jnp.inf)
    o = _mm("bhst,bthk->bshk", jax.nn.softmax(sc, -1), v, precision)
    x = x + _mm("bshk,hkd->bsd", o, p["attn"]["wo"], precision)
    y = _rms(x, p["ln2"]["scale"])
    g = _mm("bsd,df->bsf", y, p["mlp"]["wi_gate"], precision)
    u = _mm("bsd,df->bsf", y, p["mlp"]["wi_up"], precision)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["mlp"]["wo"],
                   precision)


def _ssm_layer(a: Dict, p, x, precision: str):
    b, s, _ = x.shape
    di, n, hh, hp = d_inner(a), a["ssm_state"], ssm_heads(a), a["ssm_head_dim"]
    m = p["ssm"]
    y = _rms(x, p["ln1"]["scale"])
    zxbcdt = _mm("bsd,dk->bsk", y, m["w_in"], precision)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n],
                  zxbcdt[..., 2 * di + 2 * n:])
    pad = jnp.pad(xbc, ((0, 0), (CONV_WIDTH - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + s] * m["conv_w"][i] for i in range(CONV_WIDTH))
    xbc = jax.nn.silu(conv + m["conv_b"])
    xin = xbc[..., :di].reshape(b, s, hh, hp)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dtv = jax.nn.softplus(dt + m["dt_bias"])                     # (B,S,H)
    decay = jnp.exp(dtv * -jnp.exp(m["a_log"]))                  # (B,S,H)

    def step(state, xs):                     # the plain recurrence, per token
        x_t, b_t, c_t, dt_t, g_t = xs
        state = state * g_t[..., None, None] \
            + (x_t * dt_t[..., None])[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t,
                                 precision=HIGHEST)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xin, bm, cm, dtv, decay))
    _, ys = jax.lax.scan(step, jnp.zeros((b, hh, hp, n), jnp.float32), xs)
    ys = jnp.moveaxis(ys, 0, 1) + xin * m["d_skip"][None, None, :, None]
    ys = _rms(ys.reshape(b, s, di) * jax.nn.silu(z), m["norm"])
    return x + _mm("bsk,kd->bsd", ys, m["w_out"], precision)


def make_forward(a: Dict, precision: str = "f32"):
    """A jitted fn(params, tokens (B, T) int32) -> logits (B, T, V) f32,
    run layer by layer over the stacked bfloat16 weights."""
    layer = _dense_layer if a["family"] == "dense" else _ssm_layer
    f32 = partial(jax.tree.map, lambda w: w.astype(jnp.float32))

    @jax.jit
    def forward(params, tokens):
        x = f32(params["embed"])["tok"][tokens]

        def body(x, p_l):
            return layer(a, f32(p_l), x, precision), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = _rms(x, params["lnf"]["scale"].astype(jnp.float32))
        emb = params["embed"]
        head = emb["tok"].T if a.get("tie_embeddings") else emb["unembed"]
        return _mm("bsd,dv->bsv", x, head.astype(jnp.float32), precision)

    return forward


# ----------------------------------------------------------- work
def _matmul_params(a: Dict) -> int:
    """Weights one token multiplies through: every layer's matrices and the
    output head (the embedding is a row gather)."""
    d, nl = a["d_model"], a["n_layers"]
    if a["family"] == "dense":
        h, kv, hd, f = a["n_heads"], a["n_kv_heads"], a["head_dim"], a["d_ff"]
        per = d * hd * (2 * h + 2 * kv) + 3 * d * f
    else:
        di, n, hh = d_inner(a), a["ssm_state"], ssm_heads(a)
        per = d * (2 * di + 2 * n + hh) + di * d
    return nl * per + d * a["vocab"]


def flops_per_token(a: Dict, ctx: int) -> float:
    """Model FLOPs of one token at context length ``ctx`` (the token's own
    position included): 2 per weight multiplied, plus attention over the
    context (QK^T and PV) or the SSD state update and read-out."""
    f = 2.0 * _matmul_params(a)
    if a["family"] == "dense":
        f += 4.0 * a["n_layers"] * a["n_heads"] * a["head_dim"] * ctx
    else:
        f += 6.0 * a["n_layers"] * ssm_heads(a) * a["ssm_head_dim"] \
            * a["ssm_state"]
    return f


def decode_step_bytes(a: Dict, ctxs: Sequence[int], wbytes: int = 2) -> float:
    """HBM bytes one decode step needs for live rows at contexts ``ctxs``:
    the weights once (an untied embedding only for the rows' tokens; a
    tied one is read whole as the output head), each row's live KV
    positions read and its new position written, or each row's SSD state
    (float32) and conv window read and written."""
    d, nl = a["d_model"], a["n_layers"]
    rows = len(ctxs)
    gathered = 0 if a.get("tie_embeddings") else rows * d - a["vocab"] * d
    weights = (param_count(a) + gathered) * wbytes
    if a["family"] == "dense":
        per_pos = nl * 2 * a["n_kv_heads"] * a["head_dim"] * wbytes
        return weights + per_pos * float(sum(ctxs))
    di, n = d_inner(a), a["ssm_state"]
    state = nl * ssm_heads(a) * a["ssm_head_dim"] * n * 4
    conv = nl * (CONV_WIDTH - 1) * (di + 2 * n) * wbytes
    return weights + rows * 2.0 * (state + conv)


# ----------------------------------------------------------- tiny
TINY_ARCH = {
    "dense": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab": 512},
    "ssm": {"n_layers": 2, "d_model": 64, "ssm_state": 16,
            "ssm_head_dim": 16, "ssm_chunk": 8, "vocab": 512},
}


def tiny_arch(arch: Dict) -> Dict:
    """The keys to override in one member's ``arch`` for the CPU tests
    (`chipbench/tests/tiny.py`); the cells run at published sizes."""
    return dict(TINY_ARCH[arch["family"]])
