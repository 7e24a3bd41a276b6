"""Work functions against numbers worked out by hand, and the reference's
weight layout against what the serving engine takes."""
import dataclasses

import jax
import pytest

from bench import cells, device, work

POOL = cells.load_module(cells.reference_path("danube4b-mamba2-pool"),
                         "danube4b-mamba2-pool")
V5E = device.peaks_for("TPU v5 lite")

# 1 layer, d 4, 2 query heads of 2 over 1 kv head, ff 8, vocab 10
DENSE = {"family": "dense", "n_layers": 1, "d_model": 4, "n_heads": 2,
         "n_kv_heads": 1, "head_dim": 2, "d_ff": 8, "vocab": 10}
# 1 layer, d 4, inner 8 (2 heads of 4), state 2, vocab 10
SSM = {"family": "ssm", "n_layers": 1, "d_model": 4, "ssm_expand": 2,
       "ssm_head_dim": 4, "ssm_state": 2, "vocab": 10}


def test_topn_lp_work_by_hand():
    # 8 rows x 9 arms: ranks 5*81, mask and sum 3*9 -> 432 ops a row;
    # score + cost (9 each) + n + out = 20 floats a row
    assert work.topn_lp(8, 9) == (3456.0, 640.0)


def test_awc_fw_work_by_hand():
    # gradient 6*9 = 54, each of 25 probes 5*81 + 6*9 = 459 -> 11529 a
    # row; z, mu, c, g (9 each) + 25 lambdas + 25 costs + n = 87 floats
    assert work.awc_fw(8, 9, 25) == (92232.0, 2784.0)


def test_least_seconds_takes_the_larger_bound():
    assert work.least_seconds(197e12, 1.0, V5E) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 819e9, V5E) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(device.DeviceError):
        device.peaks_for("TPU v9 imaginary")


def test_dense_counts_by_hand():
    # embed 40 + unembed 40 + lnf 4 + layer: ln 4+4, wq 16, wk 8, wv 8,
    # wo 16, mlp 96
    assert POOL.param_count(DENSE) == 236
    # 2 x (qkvo 48 + mlp 96 + head 40) = 368, attention 4*1*2*2*3 = 48
    assert POOL.flops_per_token(DENSE, 3) == 416.0
    # weights less the table plus 2 gathered rows, bf16: (196 + 8) * 2;
    # KV 1 layer * (k, v) * 1 head * 2 * 2 bytes = 8 a position, 3 + 5
    assert POOL.decode_step_bytes(DENSE, [3, 5]) == 408.0 + 64.0


def test_ssm_counts_by_hand():
    # embed 80, lnf 4, ln1 4, w_in 4*22, conv 4*12 + 12, a/d/dt 6, norm 8,
    # w_out 32
    assert POOL.param_count(SSM) == 282
    # 2 x (w_in 88 + w_out 32 + head 40) = 320; SSD 6*1*2*4*2 = 96
    assert POOL.flops_per_token(SSM, 7) == 416.0
    # weights (282 - 40 + 4) * 2 = 492; state 2*4*2 f32 = 64, conv 3*12
    # bf16 = 72, each read and written
    assert POOL.decode_step_bytes(SSM, [7]) == 492.0 + 2 * 136.0


def test_tied_ssm_counts_by_hand():
    tied = dict(SSM, tie_embeddings=True)
    # the head is the embedding: 282 less the 40 of a separate head
    assert POOL.param_count(tied) == 242
    # the head still multiplies its 40 weights
    assert POOL.flops_per_token(tied, 7) == 416.0
    # the whole table is read as the head: 242 * 2; state and conv as above
    assert POOL.decode_step_bytes(tied, [7]) == 484.0 + 2 * 136.0


@pytest.mark.parametrize("member", [0, 1])
def test_reference_layout_is_what_the_engine_takes(member):
    """The weights the benchmark makes have the program's tree and shapes
    at published widths (shapes only; nothing is allocated)."""
    from repro.configs.base import ArchConfig
    from repro.models import model as M
    m = cells.resolve("pool-suc-decode").config["members"][member]
    names = {f.name for f in dataclasses.fields(ArchConfig)}
    arch = ArchConfig(**{k: v for k, v in m["arch"].items() if k in names})
    want = jax.tree_util.tree_flatten_with_path(M.abstract_params(arch))[0]
    got = jax.tree_util.tree_flatten_with_path(
        POOL.param_tree(m["arch"]),
        is_leaf=lambda s: isinstance(s, tuple) and isinstance(s[0], tuple))[0]
    assert [(jax.tree_util.keystr(p), tuple(a.shape)) for p, a in want] == \
        [(jax.tree_util.keystr(p), s[0]) for p, s in got]
    assert POOL.param_count(m["arch"]) == M.param_count(arch)
