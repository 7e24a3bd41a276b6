"""The nemotron3nano-mamba2-pool configuration against its plain reference
at tiny widths on the CPU, with seeded random weights: the served member
(prefill, then decode through the slot cache), the held-share expert layer
(the shares add up; nothing is dropped), the grouped SSD, the layer scopes;
the member against the program's registry entry; the reference's layout
and work functions by hand.

Tolerances: program and reference both compute in float32 here, and differ
only in the order of their sums (chunked SSD against the per-token
recurrence, the held experts as one einsum against a masked dense sum), so
they agree to ~1e-5 of the outputs' spread (the expert layer's weighted sum
cancels most); each bound is 1e-4 of it, and computing in bfloat16 instead
(~1e-2) fails them."""
import contextlib
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells

NAME = "nemotron3nano-mamba2-pool"
REF = cells.load_module(cells.reference_path(NAME), NAME)
with open(os.path.join(cells.BENCH_DIR, "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
MEMBER = CONFIG["members"][0]["arch"]
TINY = dict(MEMBER, **REF.tiny_arch(MEMBER))


def arch_config(a):
    from repro.configs.base import ArchConfig
    return ArchConfig(**a)


def spread_gap(got, want):
    """Largest gap over the reference's spread."""
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / want.std())


def _serve(a, params, prompts, steps, dtype=jnp.float32):
    """Prefill, admit, then one-step decode chunks through the slot cache;
    the next-token logits after the prompt and after each served token."""
    from repro.serving.engine import Engine
    eng = Engine(arch_config(a), params, max_len=32, eos_id=-1,
                 temperature=0.0, dtype=dtype)
    state = eng.init_slots(4)
    lg, cache = eng.prefill(prompts)
    state = eng.admit(state, [1, 2], lg, cache, prompt_len=prompts.shape[1],
                      max_new=steps, seed=0)
    logits = [np.asarray(state.last[1:3])]
    for _ in range(steps - 1):
        state = eng.decode_chunk(state, 1)
        logits.append(np.asarray(state.last[1:3]))
    return np.stack(logits, 1), np.asarray(state.out[1:3, :steps - 1])


@pytest.mark.parametrize("dtype, bound", [("float32", 1e-4)])
def test_served_member_matches_the_reference_forward(dtype, bound):
    """(a) Prefill, then greedy decode through the slot cache: the logits
    after the prompt and after every served token against the reference's
    full forward over prompt and served tokens."""
    a = dict(TINY, dtype=dtype)
    params = REF.init_params(a, jax.random.PRNGKey(3), jnp.dtype(dtype))
    prompts = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0,
                                 a["vocab"], jnp.int32)
    got, served = _serve(a, params, prompts, 6, jnp.dtype(dtype))
    seq = jnp.concatenate([prompts, jnp.asarray(served, jnp.int32)], 1)
    want = REF.make_forward(a)(params, seq)[:, 7:]
    assert spread_gap(got, want) < bound


def test_bfloat16_fails_the_served_bound():
    """The same comparison with the program computing in bfloat16 from the
    same weights exceeds the bound: it is tight enough to see that."""
    a = dict(TINY, dtype="float32")
    params = REF.init_params(a, jax.random.PRNGKey(3), jnp.float32)
    prompts = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0,
                                 a["vocab"], jnp.int32)
    low = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
    got, served = _serve(dict(a, dtype="bfloat16"), low, prompts, 6,
                         jnp.bfloat16)
    seq = jnp.concatenate([prompts, jnp.asarray(served, jnp.int32)], 1)
    want = REF.make_forward(a)(params, seq)[:, 7:]
    assert spread_gap(got, want) > 1e-4


def _moe_params(a, key):
    return jax.tree.map(lambda w: w[0], REF.init_params(
        dict(a, layer_pattern="E", n_layers=1), key, jnp.float32)["moe"])


def _shared(p, xn):
    return jnp.square(jax.nn.relu(xn @ p["moe"]["shared_up"])) \
        @ p["moe"]["shared_down"]


def test_shares_add_up_to_the_uncut_layer():
    """(b) n_router 8 in 4 shares of 2: the shares' outputs, with the
    shared expert counted once, sum to the uncut reference layer."""
    from repro.models import moe
    a = dict(TINY, n_experts=8, n_router_experts=8, top_k=3)
    p = _moe_params(a, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, a["d_model"]))
    xn = REF._rms(x, p["ln1"]["scale"], a["norm_eps"])
    total = -3 * _shared(p, xn)
    for e0 in range(0, 8, 2):
        share = {k: v[e0:e0 + 2] if k in ("up", "down") else v
                 for k, v in p["moe"].items()}
        cfg = arch_config(dict(a, n_experts=2, expert_offset=e0))
        total = total + moe.apply_held_moe(cfg, share, xn)
    want = REF._moe_layer(a, p, x, "f32") - x
    assert spread_gap(total, want) < 1e-4


def test_no_assignment_is_dropped():
    """(c) Every token routed to held expert 0 (its correction bias far
    above the others'): the layer still gives the reference's output, where
    a capacity of 1.25 x tokens x k / experts would drop most of them."""
    from repro.models import moe
    a = dict(TINY, n_experts=4, n_router_experts=8, top_k=2)
    p = _moe_params(a, jax.random.PRNGKey(7))
    p["moe"]["router_bias"] = p["moe"]["router_bias"].at[0].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, a["d_model"]))
    xn = REF._rms(x, p["ln1"]["scale"], a["norm_eps"])
    cw = moe.route_held(arch_config(a), p["moe"], xn.reshape(32, -1))
    assert bool((cw[:, 0] > 0).all())               # all 32 tokens to e0
    got = moe.apply_held_moe(arch_config(a), p["moe"], xn)
    want = REF._moe_layer(a, p, x, "f32") - x
    assert spread_gap(got, want) < 1e-4


def test_held_layer_runs_long_prefills_in_token_blocks(monkeypatch):
    from repro.models import moe
    a = dict(TINY, n_experts=4, n_router_experts=8, top_k=2)
    p = _moe_params(a, jax.random.PRNGKey(9))
    xn = jax.random.normal(jax.random.PRNGKey(10), (3, 7, a["d_model"]))
    whole = moe.apply_held_moe(arch_config(a), p["moe"], xn)
    monkeypatch.setattr(moe, "HELD_TOKEN_BLOCK", 4)      # 21 tokens: 6 blocks
    blocked = moe.apply_held_moe(arch_config(a), p["moe"], xn)
    np.testing.assert_allclose(blocked, whole, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("g", [8, 1])
def test_grouped_ssd_matches_the_recurrence(g):
    """(d) The Mamba-2 layer (chunked SSD, B/C in g groups, group-wise gated
    norm) against the reference's per-token recurrence."""
    from repro.models import ssm
    a = dict(TINY, ssm_groups=g, layer_pattern="M", n_layers=1)
    p = jax.tree.map(lambda w: w[0], REF.init_params(
        a, jax.random.PRNGKey(11), jnp.float32)["mamba"])
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 16, a["d_model"]))
    xn = REF._rms(x, p["ln1"]["scale"], a["norm_eps"])
    got = ssm.apply_ssm(arch_config(a), p["ssm"], xn)
    want = REF._mamba_layer(a, p, x, "f32") - x
    assert spread_gap(got, want) < 1e-4


def test_one_group_is_today_s_single_group_path():
    """(d) B/C of one group through the grouped (vmapped) form equal the
    single-group SSD bit for bit, state included."""
    from repro.models import ssm
    k = jax.random.split(jax.random.PRNGKey(13), 5)
    xh = jax.random.normal(k[0], (2, 16, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, 16, 4)))
    a = -jnp.exp(jax.random.normal(k[2], (4,)))
    bm, cm = (jax.random.normal(k[i], (2, 16, 6)) for i in (3, 4))
    y1, s1 = ssm.ssd(xh, dt, a, bm, cm, 8, return_state=True)
    yg, sg = ssm.ssd(xh, dt, a, bm[:, :, None], cm[:, :, None], 8,
                     return_state=True)
    np.testing.assert_array_equal(np.asarray(yg), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(sg), np.asarray(s1))


def test_layer_scopes_leave_outputs_bit_equal(monkeypatch):
    """(e) The ssm_layer / moe_layer / attn_layer scopes name the ops (they
    are in the lowered program's locations) and change no output."""
    from repro.models import model as M
    cfg = arch_config(TINY)
    params = REF.init_params(TINY, jax.random.PRNGKey(14), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(15), (2, 8), 0, 512)

    def run():
        last, cache = jax.jit(lambda p, t: M.prefill(
            cfg, p, {"tokens": t}, 16))(params, toks)
        lg, _ = jax.jit(lambda p, t, c: M.decode_step(
            cfg, p, t, c, jnp.full((2,), 8, jnp.int32)))(
                params, toks[:, :1], cache)
        return last, lg

    text = jax.jit(lambda p, t: M.prefill(cfg, p, {"tokens": t}, 16)).lower(
        params, toks).as_text(debug_info=True)
    for scope in ("ssm_layer", "moe_layer", "attn_layer"):
        assert scope in text
    on = run()
    monkeypatch.setattr(M, "_scope", lambda name: contextlib.nullcontext())
    off = run()
    for a, b in zip(on, off):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_member_is_the_registry_model_cut_as_stated():
    """The served member is the registry's nemotron-3-nano-30b-a3b with only
    the keys BENCHMARK.json lists as reduced changed, each as the file's
    `published` and `deployment` say."""
    from repro.configs.base import get_config
    bench = cells.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    as_arch = {"num_hidden_layers": "n_layers",
               "hybrid_override_pattern": "layer_pattern",
               "n_routed_experts": "n_experts"}
    full = dataclasses.asdict(get_config("nemotron-3-nano-30b-a3b"))
    served = dataclasses.asdict(arch_config(MEMBER))
    changed = {k for k in full if full[k] != served[k]} - {"source"}
    assert changed == {as_arch[k] for k in entry["reduced"]}
    for k in entry["reduced"]:
        assert CONFIG["published"][k] == full[as_arch[k]]
        assert CONFIG[k] == served[as_arch[k]]
    assert full["layer_pattern"].startswith(served["layer_pattern"])
    # the catalog's own keys, as published, where the member reads them
    for key, field in [("hidden_size", "d_model"), ("vocab_size", "vocab"),
                       ("num_attention_heads", "n_heads"),
                       ("num_key_value_heads", "n_kv_heads"),
                       ("head_dim", "head_dim"), ("ssm_state_size",
                                                  "ssm_state"),
                       ("mamba_head_dim", "ssm_head_dim"),
                       ("mamba_num_heads", "ssm_n_heads"),
                       ("n_groups", "ssm_groups"), ("chunk_size",
                                                    "ssm_chunk"),
                       ("moe_intermediate_size", "moe_d_ff"),
                       ("moe_shared_expert_intermediate_size",
                        "shared_expert_d_ff"),
                       ("num_experts_per_tok", "top_k"),
                       ("routed_scaling_factor", "routed_scale"),
                       ("norm_eps", "norm_eps")]:
        assert CONFIG[key] == served[field], key
    assert CONFIG["published"]["n_routed_experts"] == \
        served["n_router_experts"]


@pytest.mark.parametrize("member", [0, 1])
def test_reference_layout_is_what_the_engine_takes(member):
    """Shapes only, at published widths (nothing is allocated)."""
    from repro.models import model as M
    a = CONFIG["members"][member]["arch"]
    want = jax.tree_util.tree_flatten_with_path(
        M.abstract_params(arch_config(a)))[0]
    got = jax.tree_util.tree_flatten_with_path(
        REF.param_tree(a), is_leaf=REF._is_spec)[0]
    assert [(jax.tree_util.keystr(p), tuple(x.shape)) for p, x in want] == \
        [(jax.tree_util.keystr(p), s[0]) for p, s in got]
    assert REF.param_count(a) == M.param_count(arch_config(a))


# 1 of each layer kind, d 4: Mamba-2 of 2 heads of 2 in 2 groups, state 2;
# 4 held of 8 router experts, top 2, expert width 3, shared 5; GQA 2 query
# heads over 1 kv head of 2; vocab 10, untied
HAND = {"family": "hybrid_moe", "n_layers": 3, "layer_pattern": "ME*",
        "d_model": 4, "vocab": 10, "ssm_state": 2, "ssm_head_dim": 2,
        "ssm_n_heads": 2, "ssm_groups": 2, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 2, "n_experts": 4, "n_router_experts": 8, "top_k": 2,
        "moe_d_ff": 3, "shared_expert_d_ff": 5}


def test_work_by_hand():
    # Mamba-2: w_in 4 x (2*4 + 2*4 + 2) = 72, out 4*4 = 16 -> 88; expert
    # layer: router 32, shared 2*4*5 = 40, routed 2*4/8 = 1 expert of 24
    # -> 96; attention 4*2*(4 + 2) = 48; head 40
    # FLOPs: 2*(88 + 96 + 48 + 40) = 544; attention 4*1*2*2*ctx(3) = 48;
    # SSD 6*1*2*2*2 = 48
    assert REF.flops_per_token(HAND, 3) == 544.0 + 48.0 + 48.0
    # one row hits 4 * (1 - (1 - 2/8)^1) = 1 held expert; two rows 1.75
    assert REF.held_experts_hit(HAND, 1) == pytest.approx(1.0)
    assert REF.held_experts_hit(HAND, 2) == pytest.approx(1.75)
    # params: embed 40 + unembed 40 + lnf 4; Mamba-2 ln 4, w_in 72, conv
    # (inner 4 + B/C 2*2*2 = 12 channels) 4*12 + 12, a/d/dt 6, norm 4, out
    # 16 -> 162; experts ln 4, router 32, bias 8, up/down 4*24 = 96, shared
    # 40 -> 180; attention ln 4, q 16, k 8, v 8, o 16 -> 52
    assert REF.param_count(HAND) == 84 + 162 + 180 + 52
    # two rows at contexts 3 and 5, bf16: weights less the table plus 2
    # rows, less the 2.25 experts not expected hit: (478 - 40 + 8 - 54) * 2
    # = 784; KV 1 layer * (k, v) * 1 head * 2 * 2 bytes = 8 a position ->
    # 64; per row SSD state 2*2*2 f32 = 32 and conv 3*12 bf16 = 72, each
    # read and written -> 2 * 2 * 104 = 416
    assert REF.decode_step_bytes(HAND, [3, 5]) == pytest.approx(
        784.0 + 64.0 + 416.0)
