"""Plain reference for the nemotron3nano-mamba2-pool configuration.

nemotron-3-nano-30b-a3b (the cut of one chip: layers 1-27 of the published
pattern, 16 of the 128 routed experts) and mamba2-780m, written out in
straightforward `jax.numpy` at float32 with `precision="highest"`. It
imports nothing of the program under test.

  Mamba-2 layer  in_proj -> [z | xBC | dt]; depthwise causal conv (width 4,
                 bias) over xBC, silu; dt = softplus(dt + dt_bias),
                 A = -exp(A_log); the per-token recurrence
                 h = exp(dt A) h + dt x B^T, y = h C + D x, head h reading
                 B/C group h // (H/G); RMSNorm of y * silu(z) over each of
                 the G groups; out_proj.
  expert layer   sigmoid scores s of all 128 router outputs (float32); the
                 top 6 of s + correction bias; weights s of those, over
                 their sum, times 2.5; the held experts computed densely
                 over every token and masked by those weights
                 (down(relu(up x)^2)), plus the shared expert.
  attention      causal GQA, no position embedding (NoPE).
  every layer    x + mixer(RMSNorm(x)), eps from `norm_eps`.

It also makes the weights: one jitted call per member draws every leaf
from the run's seed, in the dtype the configuration serves (bfloat16), in
the layout the serving engine takes (one stack per layer kind). The
reference reads those same bfloat16 values, cast to float32 one layer at a
time.

`make_forward` runs layer by layer, a few rows at a time, and returns the
final hidden states with the output head: indexing them (rows, positions)
gives those positions' float32 logits over the whole vocabulary, computed
in vocabulary blocks and returned as a host array, so that the published
131072-word head fits beside the served weights.

`precision="fp8"` is the control: every matrix-multiply operand (weights,
activations, the router's and the attention's operands) rounded to float8
e4m3, the nearest precision below the configuration's bfloat16.

The work functions give the FLOPs and bytes one token needs, for the
per-layer metrics `decode_roofline` and `served_mfu`: the expert layers
count the router, the shared expert and the held experts a step's R rows
are expected to hit under uniform routing, n_held (1 - (1 - k/n_router)^R),
and per token k n_held / n_router routed experts. `tiny_arch` at the end
gives each member's sizes for the CPU tests.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

CONV_WIDTH = 4
HIGHEST = jax.lax.Precision.HIGHEST
ROWS_AT_ONCE = 4            # rows of a forward run through the layers at once
VOCAB_BLOCK = 16384         # output-head columns computed at once
KIND = {"M": "mamba", "E": "moe", "*": "attn"}


# ----------------------------------------------------------- sizes
def d_inner(a: Dict) -> int:
    if a.get("ssm_n_heads"):
        return a["ssm_n_heads"] * a["ssm_head_dim"]
    return a["ssm_expand"] * a["d_model"]


def ssm_heads(a: Dict) -> int:
    return a.get("ssm_n_heads") or d_inner(a) // a["ssm_head_dim"]


def groups(a: Dict) -> int:
    return a.get("ssm_groups", 1)


def pattern(a: Dict) -> str:
    return a.get("layer_pattern") or "M" * a["n_layers"]


def n_router(a: Dict) -> int:
    return a.get("n_router_experts") or a["n_experts"]


# ----------------------------------------------------------- weights
def _mamba_tree(a: Dict, std: float, std_o: float) -> Dict:
    d, di, n, hh = a["d_model"], d_inner(a), a["ssm_state"], ssm_heads(a)
    gn = groups(a) * n
    conv_ch = di + 2 * gn
    return {"ln1": {"scale": ((d,), "ones", 0.0)},
            "ssm": {"w_in": ((d, 2 * di + 2 * gn + hh), "normal", std),
                    "conv_w": ((CONV_WIDTH, conv_ch), "normal", std),
                    "conv_b": ((conv_ch,), "normal", std),
                    "a_log": ((hh,), "ones", 0.0),
                    "d_skip": ((hh,), "ones", 0.0),
                    "dt_bias": ((hh,), "zeros", 0.0),
                    "norm": ((di,), "ones", 0.0),
                    "w_out": ((di, d), "normal", std_o)}}


def _moe_tree(a: Dict, std: float, std_o: float) -> Dict:
    d, e, f, fs = (a["d_model"], a["n_experts"], a["moe_d_ff"],
                   a["shared_expert_d_ff"])
    return {"ln1": {"scale": ((d,), "ones", 0.0)},
            "moe": {"router": ((d, n_router(a)), "normal", std),
                    "router_bias": ((n_router(a),), "normal", std),
                    "up": ((e, d, f), "normal", std),
                    "down": ((e, f, d), "normal", std_o),
                    "shared_up": ((d, fs), "normal", std),
                    "shared_down": ((fs, d), "normal", std_o)}}


def _attn_tree(a: Dict, std: float, std_o: float) -> Dict:
    d, h, kv, hd = a["d_model"], a["n_heads"], a["n_kv_heads"], a["head_dim"]
    return {"ln1": {"scale": ((d,), "ones", 0.0)},
            "attn": {"wq": ((d, h, hd), "normal", std),
                     "wk": ((d, kv, hd), "normal", std),
                     "wv": ((d, kv, hd), "normal", std),
                     "wo": ((h, hd, d), "normal", std_o)}}


def _stack(tree, n: int):
    return jax.tree.map(lambda s: ((n,) + s[0], s[1], s[2]), tree,
                        is_leaf=_is_spec)


def param_tree(a: Dict) -> Dict:
    """The serving layout: {path: (shape, init, std)}. Normal draws use std
    0.02, output projections 0.02/sqrt(2L)."""
    d, v, nl = a["d_model"], a["vocab"], a["n_layers"]
    std, std_o = 0.02, 0.02 / math.sqrt(2 * nl)
    t = {"embed": {"tok": ((v, d), "normal", std)},
         "lnf": {"scale": ((d,), "ones", 0.0)}}
    if not a.get("tie_embeddings"):
        t["embed"]["unembed"] = ((d, v), "normal", std)
    if a["family"] == "ssm":
        t["layers"] = _stack(_mamba_tree(a, std, std_o), nl)
    elif a["family"] == "hybrid_moe":
        trees = {"M": _mamba_tree, "E": _moe_tree, "*": _attn_tree}
        for ch in sorted(set(pattern(a))):
            t[KIND[ch]] = _stack(trees[ch](a, std, std_o),
                                 pattern(a).count(ch))
    else:
        raise ValueError(f"no reference for family {a['family']!r}")
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


def _rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _mamba_layer(a: Dict, p, x, precision: str):
    b, s, _ = x.shape
    di, n, hh, hp = d_inner(a), a["ssm_state"], ssm_heads(a), a["ssm_head_dim"]
    g, eps = groups(a), a.get("norm_eps", 1e-6)
    m = p["ssm"]
    y = _rms(x, p["ln1"]["scale"], eps)
    zxbcdt = _mm("bsd,dk->bsk", y, m["w_in"], precision)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * g * n],
                  zxbcdt[..., 2 * di + 2 * g * n:])
    pad = jnp.pad(xbc, ((0, 0), (CONV_WIDTH - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + s] * m["conv_w"][i] for i in range(CONV_WIDTH))
    xbc = jax.nn.silu(conv + m["conv_b"])
    xin = xbc[..., :di].reshape(b, s, hh, hp)
    head_group = jnp.arange(hh) // (hh // g)
    bm = xbc[..., di:di + g * n].reshape(b, s, g, n)[:, :, head_group]
    cm = xbc[..., di + g * n:].reshape(b, s, g, n)[:, :, head_group]
    dtv = jax.nn.softplus(dt + m["dt_bias"])                     # (B,S,H)
    decay = jnp.exp(dtv * -jnp.exp(m["a_log"]))                  # (B,S,H)

    def step(state, xs):                     # the plain recurrence, per token
        x_t, b_t, c_t, dt_t, g_t = xs
        state = state * g_t[..., None, None] \
            + (x_t * dt_t[..., None])[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=HIGHEST)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xin, bm, cm, dtv, decay))
    _, ys = jax.lax.scan(step, jnp.zeros((b, hh, hp, n), jnp.float32), xs)
    ys = jnp.moveaxis(ys, 0, 1) + xin * m["d_skip"][None, None, :, None]
    ys = ys.reshape(b, s, di) * jax.nn.silu(z)
    ys = _rms(ys.reshape(b, s, g, di // g), m["norm"].reshape(g, -1),
              eps).reshape(b, s, di)
    return x + _mm("bsk,kd->bsd", ys, m["w_out"], precision)


def _moe_layer(a: Dict, p, x, precision: str):
    m, eps = p["moe"], a.get("norm_eps", 1e-6)
    y = _rms(x, p["ln1"]["scale"], eps)
    scores = jax.nn.sigmoid(_mm("bsd,de->bse", y, m["router"], precision))
    _, idx = jax.lax.top_k(scores + m["router_bias"], a["top_k"])
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * a["routed_scale"]
    held = a.get("expert_offset", 0) + jnp.arange(a["n_experts"])
    cw = jnp.einsum("bsk,bske->bse", w,
                    (idx[..., None] == held).astype(jnp.float32))
    h = jnp.square(jax.nn.relu(_mm("bsd,edf->bsef", y, m["up"], precision)))
    out = _mm("bsef,efd->bsd", h * cw[..., None], m["down"], precision)
    hs = jnp.square(jax.nn.relu(_mm("bsd,df->bsf", y, m["shared_up"],
                                    precision)))
    return x + out + _mm("bsf,fd->bsd", hs, m["shared_down"], precision)


def _attn_layer(a: Dict, p, x, precision: str):
    b, s, _ = x.shape
    h, kv, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    at = p["attn"]
    y = _rms(x, p["ln1"]["scale"], a.get("norm_eps", 1e-6))
    q = _mm("bsd,dhk->bshk", y, at["wq"], precision)
    k = _mm("bsd,dhk->bshk", y, at["wk"], precision)
    v = _mm("bsd,dhk->bshk", y, at["wv"], precision)
    group = jnp.arange(h) // (h // kv)             # query head -> kv head
    k, v = k[:, :, group], v[:, :, group]
    sc = _mm("bshk,bthk->bhst", q, k, precision) / math.sqrt(hd)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(mask, sc, -jnp.inf)
    o = _mm("bhst,bthk->bshk", jax.nn.softmax(sc, -1), v, precision)
    return x + _mm("bshk,hkd->bsd", o, at["wo"], precision)


LAYERS = {"M": _mamba_layer, "E": _moe_layer, "*": _attn_layer}


class Logits:
    """Final hidden states (B, T, D) f32 and the output head. Indexing
    selects rows and positions; the result is their float32 logits over the
    whole vocabulary, as a host array."""

    def __init__(self, hidden, head, head_fn):
        self.hidden, self.head, self.head_fn = hidden, head, head_fn
        self.shape = tuple(hidden.shape[:2]) + (head.shape[1],)

    def __getitem__(self, key):
        h = self.hidden[key]
        v = self.head.shape[1]
        block = min(VOCAB_BLOCK, v)
        out = np.empty(tuple(h.shape[:-1]) + (v,), np.float32)
        for lo in range(0, v, block):
            lo = min(lo, v - block)        # the last block ends at v
            out[..., lo:lo + block] = np.asarray(
                self.head_fn(h, self.head, jnp.int32(lo), block))
        return out


def make_forward(a: Dict, precision: str = "f32"):
    """fn(params, tokens (B, T) int32) -> `Logits`; index it for the
    logits (B', T', V) f32. Layer by layer (each layer's bfloat16 weights
    cast to float32 inside its own call), ROWS_AT_ONCE rows at a time."""
    f32 = partial(jax.tree.map, lambda w: w.astype(jnp.float32))
    kinds = {"M": "layers"} if a["family"] == "ssm" else KIND

    def jit_layer(ch):
        @jax.jit
        def run(stack, j, x):
            p = jax.tree.map(
                lambda w: jax.lax.dynamic_index_in_dim(w, j, keepdims=False),
                stack)
            return LAYERS[ch](a, f32(p), x, precision)
        return run

    run = {ch: jit_layer(ch) for ch in set(pattern(a))}

    @jax.jit
    def embed(tok, tokens):
        return tok[tokens].astype(jnp.float32)     # gather, then cast

    @jax.jit
    def final(scale, x):
        return _rms(x, scale.astype(jnp.float32), a.get("norm_eps", 1e-6))

    @partial(jax.jit, static_argnums=3)
    def head_fn(h, head, lo, block):
        w = jax.lax.dynamic_slice_in_dim(head, lo, block, axis=1)
        return _mm("...d,dv->...v", h, w.astype(jnp.float32), precision)

    def forward(params, tokens):
        tokens = jnp.asarray(tokens, jnp.int32)
        outs = []
        for r0 in range(0, tokens.shape[0], ROWS_AT_ONCE):
            x = embed(params["embed"]["tok"], tokens[r0:r0 + ROWS_AT_ONCE])
            seen = {ch: 0 for ch in run}
            for ch in pattern(a):
                x = run[ch](params[kinds[ch]], seen[ch], x)
                seen[ch] += 1
            outs.append(final(params["lnf"]["scale"], x))
        emb = params["embed"]
        head = emb["tok"].T if a.get("tie_embeddings") else emb["unembed"]
        return Logits(jnp.concatenate(outs), head, head_fn)

    return forward


# ----------------------------------------------------------- work
def _layer_matmul_params(a: Dict, ch: str) -> float:
    """Weights one token multiplies through in one layer of kind ``ch``;
    the expert layer's routed experts as the expected k n_held / n_router
    of them."""
    d = a["d_model"]
    if ch == "M":
        di, gn, hh = d_inner(a), groups(a) * a["ssm_state"], ssm_heads(a)
        return d * (2 * di + 2 * gn + hh) + di * d
    if ch == "*":
        h, kv, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
        return d * hd * (2 * h + 2 * kv)
    routed = a["top_k"] * a["n_experts"] / n_router(a)
    return d * n_router(a) + 2 * d * a["shared_expert_d_ff"] \
        + routed * 2 * d * a["moe_d_ff"]


def flops_per_token(a: Dict, ctx: int) -> float:
    """Model FLOPs of one token at context length ``ctx`` (the token's own
    position included): 2 per weight multiplied (the output head included;
    the embedding is a row gather), plus attention over the context (QK^T
    and PV) and the SSD state update and read-out."""
    pat = pattern(a)
    f = 2.0 * (sum(_layer_matmul_params(a, ch) for ch in pat)
               + a["d_model"] * a["vocab"])
    f += 4.0 * pat.count("*") * a.get("n_heads", 0) * a.get("head_dim", 0) \
        * ctx
    f += 6.0 * pat.count("M") * ssm_heads(a) * a["ssm_head_dim"] \
        * a["ssm_state"]
    return f


def held_experts_hit(a: Dict, rows: int) -> float:
    """Expected held experts one expert layer's step reads for ``rows``
    tokens, each routed to top_k of n_router uniformly."""
    return a["n_experts"] * (1.0 - (1.0 - a["top_k"] / n_router(a)) ** rows)


def decode_step_bytes(a: Dict, ctxs: Sequence[int], wbytes: int = 2) -> float:
    """HBM bytes one decode step needs for live rows at contexts ``ctxs``:
    the weights once, the routed experts only as far as the rows are
    expected to hit them (`held_experts_hit`), an untied embedding only
    for the rows' tokens (a tied one is read whole as the output head);
    each row's live KV positions read and its new position written; each
    row's SSD state (float32) and conv window read and written."""
    d, pat = a["d_model"], pattern(a)
    rows = len(ctxs)
    gathered = 0 if a.get("tie_embeddings") else rows * d - a["vocab"] * d
    params = param_count(a) + gathered
    n_e = pat.count("E")
    if n_e:
        expert = 2 * d * a["moe_d_ff"]
        params += n_e * expert * (held_experts_hit(a, rows) - a["n_experts"])
    nbytes = params * wbytes
    n_a = pat.count("*")
    if n_a:
        nbytes += n_a * 2 * a["n_kv_heads"] * a["head_dim"] * wbytes \
            * float(sum(ctxs))
    n_m = pat.count("M")
    di, n = d_inner(a), a["ssm_state"]
    state = n_m * ssm_heads(a) * a["ssm_head_dim"] * n * 4
    conv = n_m * (CONV_WIDTH - 1) * (di + 2 * groups(a) * n) * wbytes
    return nbytes + rows * 2.0 * (state + conv)


# ----------------------------------------------------------- tiny
TINY_ARCH = {
    "ssm": {"n_layers": 2, "d_model": 64, "ssm_state": 16,
            "ssm_head_dim": 16, "ssm_chunk": 8, "vocab": 512},
    "hybrid_moe": {"n_layers": 5, "layer_pattern": "MEME*", "d_model": 64,
                   "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
                   "vocab": 512, "n_experts": 4, "n_router_experts": 8,
                   "top_k": 2, "moe_d_ff": 32, "shared_expert_d_ff": 64,
                   "ssm_state": 16, "ssm_head_dim": 16, "ssm_n_heads": 8,
                   "ssm_groups": 2, "ssm_chunk": 8},
}


def tiny_arch(arch: Dict) -> Dict:
    """The keys to override in one member's ``arch`` for the CPU tests
    (`chipbench/tests/tiny.py`); the cells run at published sizes."""
    return dict(TINY_ARCH[arch["family"]])
