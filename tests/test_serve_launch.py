"""The served entry point (`repro.launch.serve`) builds its pool at the
widths and dtype of the configs it is given; `chip_smoke.py`'s served phase,
which drives that entry point and checks what comes out, passes on the CPU
at reduced widths; the entry points' compile cache goes where it should."""
import dataclasses
import os

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.launch import compile_cache, serve

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
POOL = ("h2o-danube-3-4b", "mamba2-780m")


def reduced_bf16():
    return [dataclasses.replace(get_config(nm).reduced(), dtype="bfloat16")
            for nm in POOL]


def test_build_service_uses_the_given_configs():
    cfgs = reduced_bf16()
    args = serve.parse_args(["--tenants", "2", "--max-len", "32"])
    runner, svc, names = serve.build_service(args, cfgs)
    assert runner.tenants[0] is svc
    assert names == [c.name for c in cfgs]
    for rep, cfg in zip(svc.cloud.replicas, cfgs):
        assert rep.engine.cfg == cfg
        assert rep.engine.max_len == 32
        assert jnp.dtype(rep.engine.dtype) == jnp.bfloat16
        assert {x.dtype for x in jax.tree.leaves(rep.engine.params)} == {
            jnp.dtype(jnp.bfloat16)}
    assert [r.n_slots for r in svc.sched.runners] == [serve.SLOTS] * 2


def test_report_reads_the_scheduler_counters():
    cfgs = reduced_bf16()
    args = serve.parse_args(["--tenants", "2", "--max-len", "32",
                             "--kind", "suc"])
    runner, svc, names = serve.build_service(args, cfgs)
    runner.run(1)
    stats = svc.sched.stats()
    lines = serve.counter_lines(names, stats)
    assert len(lines) == 2
    for nm, st, line in zip(names, stats, lines):
        # SUC with n = k: every tenant asks every member once a round
        assert st["admitted"] == 2
        assert st["tokens_out"] == sum(
            int(c.result.out_lens.sum()) for c in runner.last_completions
            if c.request.arm == names.index(nm))
        wait_ms = 1e3 * st["queue_wait_s"] / 2
        per_step = st["tokens_out"] / st["slot_steps"]
        assert line.startswith(f"  {nm}: queue wait {wait_ms:.2f} ms mean "
                               f"over 2 admitted, {st['tokens_out']} tokens "
                               f"out, {per_step:.3f} tokens/slot-step")
        assert "failures 0 retries 0" in line


def test_chip_smoke_served_phase_on_cpu(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    rec = chip_smoke.phase_served(reduced_bf16(), max_len=64, rounds=2)
    assert rec["requests_answered"] > 0
    assert rec["param_dtype"] == ["bfloat16"]
    assert rec["cache_dtype"] == ["bfloat16"]
    assert set(rec["vs_forward"]) == {c.name for c in reduced_bf16()}


def test_compile_cache_leaves_the_variable_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() \
            == compile_cache.CHECKOUT_CACHE
        assert jax.config.jax_compilation_cache_dir \
            == compile_cache.CHECKOUT_CACHE
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert compile_cache.CHECKOUT_CACHE == os.path.join(ROOT, ".jax_cache")
