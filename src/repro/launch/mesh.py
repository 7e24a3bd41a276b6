"""Mesh builders + the fleet tenant mesh.

Mesh builders are functions, not module constants, so importing this module
never touches jax device state. Every mesh is built by `make_mesh` with
Auto axes: `jax.make_mesh` defaults to Explicit axes, under which the
`with_sharding_constraint` of `sharding.shard` acts as an assert.

Run as a module this is the real-mesh fleet smoke: it builds an N-device
`(pod, data)` mesh, advances a small fleet through the sharded scan, and
verifies the trajectory bit-for-bit against the single-device reference.
Without ``--devices`` the mesh spans the attached devices (the chips of a
TPU host); ``--devices N`` instead forces N virtual CPU devices and runs
mesh and reference on the CPU only:

  PYTHONPATH=src python -m repro.launch.mesh --devices 8 --tenants 64 \
      --rounds 32 [--pods 2] [--workload mixed] [--ckpt-dir DIR]
"""
from __future__ import annotations

import sys

if __name__ == "__main__" and "--devices" in sys.argv:
    # must precede the jax import below: the device count locks at init
    from repro.launch.hostdev import force_host_device_count
    force_host_device_count(int(sys.argv[sys.argv.index("--devices") + 1]))

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with every axis Auto (see the module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh():
    """Degenerate 1-device mesh for CPU smoke paths."""
    return make_mesh((1, 1), ("data", "model"))


def make_fleet_mesh(devices=None, *, pods: int = 1):
    """Tenant mesh for the sharded fleet scan (router.fleet): all
    ``devices`` (default: every attached device) on the `(pod, data)` axes
    the "tenants" logical axis shards over."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    if pods > 1:
        if n % pods:
            raise ValueError(f"{n} devices don't split into {pods} pods")
        return make_mesh((pods, n // pods), ("pod", "data"), devices)
    return make_mesh((n,), ("data",), devices)


def n_chips(mesh) -> int:
    return mesh.devices.size


# ============================================================ fleet smoke
def _peak_bytes(devices):
    """Per-device peak memory where the backend reports it (None on CPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(st is None for st in stats):
        return None
    return [int(st.get("peak_bytes_in_use", 0)) for st in stats]


def fleet_smoke(devices, tenants: int, rounds: int, *, pods: int = 1,
                workload: str = "mixed", ckpt_dir=None, ckpt_every: int = 0,
                seed: int = 0) -> dict:
    """Sharded fleet run on a mesh of ``devices``, verified bit-for-bit
    against the single-device reference on ``devices[0]``. Returns a
    summary record (printed as JSON by the CLI). ``peak_bytes`` is each
    device's peak memory right after the sharded run — every device of the
    mesh must have held its share of the tenants, not just the first."""
    import time

    import numpy as np

    from repro.core.policies import PolicyConfig
    from repro.env.llm_profiles import default_rho, paper_pool
    from repro.router import fleet

    devices = list(devices)
    pool = paper_pool("sciq")
    kinds = [("awc", "suc", "aic")[i % 3] for i in range(tenants)] \
        if workload == "mixed" else [workload] * tenants
    pcfgs = [PolicyConfig(kind=k, k=pool.k, n=4,
                          rho=default_rho(pool, k, 4), delta=1.0 / rounds)
             for k in kinds]
    mesh = make_fleet_mesh(devices, pods=pods)
    axes = fleet.fleet_mesh_axes(tenants, mesh)
    with jax.default_device(devices[0]):
        cfg = fleet.fleet_config(pcfgs)
        keys = jax.random.split(jax.random.PRNGKey(seed), tenants)
        t0 = time.perf_counter()
        sharded = fleet.simulate_fleet(pool, cfg, T=rounds, keys=keys,
                                       mesh=mesh, ckpt_dir=ckpt_dir,
                                       ckpt_every=ckpt_every)
        dt_sharded = time.perf_counter() - t0
        peaks = _peak_bytes(devices)
        t0 = time.perf_counter()
        ref = fleet.simulate_fleet(pool, cfg, T=rounds, keys=keys)
        dt_single = time.perf_counter() - t0

    bit_equal = (
        np.array_equal(sharded.action, ref.action[:, sharded.t0:])
        and np.array_equal(sharded.observed, ref.observed[:, sharded.t0:])
        and np.array_equal(sharded.cost, ref.cost[:, sharded.t0:])
        and all(np.array_equal(sharded.state.stats[n], ref.state.stats[n])
                for n in ref.state.stats)
        and np.array_equal(sharded.state.key, ref.state.key))
    return {"devices": n_chips(mesh), "platform": devices[0].platform,
            "pods": pods, "tenants": tenants, "rounds": rounds,
            "workload": workload,
            "tenant_axes": list(axes) if axes else None,
            "sharded": axes is not None, "bit_equal": bool(bit_equal),
            "peak_bytes": peaks,
            # first calls: compilation included
            "seconds_sharded": dt_sharded, "seconds_single": dt_single}


def _main(argv=None):
    import argparse
    import json

    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description="real-mesh fleet smoke")
    ap.add_argument("--devices", type=int, default=0,
                    help="run on N virtual CPU devices (0 = the attached "
                         "devices)")
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--tenants", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=32)
    ap.add_argument("--workload", default="mixed",
                    choices=["awc", "suc", "aic", "mixed"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    devices = jax.devices("cpu") if args.devices else jax.devices()
    rec = fleet_smoke(devices, args.tenants, args.rounds,
                      pods=args.pods, workload=args.workload,
                      ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                      seed=args.seed)
    print(json.dumps(rec))
    if not rec["bit_equal"]:
        raise SystemExit("sharded fleet diverged from the single-device "
                         "reference")


if __name__ == "__main__":
    _main()
