import functools
import os

# Smoke tests and benches see ONE device; only the dry-run forces 512.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def tpu_lowering(monkeypatch):
    """Route the relax grid engine through its TPU lowering — the wide
    G-way search over the Pallas `topn_lp`/`awc_fw` kernels — with the
    kernels in interpret mode, so the CPU runs the chip's code path."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "topn_lp",
                        functools.partial(ops.topn_lp, interpret=True))
    monkeypatch.setattr(ops, "awc_fw",
                        functools.partial(ops.awc_fw, interpret=True))
