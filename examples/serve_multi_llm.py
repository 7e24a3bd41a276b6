"""End-to-end driver: the C2MAB-V router serving REAL JAX models.

Builds a pool of three reduced-architecture pool members (one trained on the
query stream, two untrained), deploys them behind the scheduling cloud, and
runs the full local-cloud protocol: relax -> round -> dispatch -> generate ->
measure quality -> Eq.(6) update. The router learns to cascade to the
trained (cheap, good) model and stops querying the expensive ones.

Generation is served by the continuous-batching engine: four tenants share
the pool, so each round their requests coalesce into per-replica slot-cache
decode batches and bandit feedback is applied asynchronously as each
completion lands (paper App. E.3). The members are the CPU-sized
`.reduced()` variants; `python -m repro.launch.serve` builds the published
widths instead.

  PYTHONPATH=src python examples/serve_multi_llm.py
"""
import dataclasses

from repro.configs.base import get_config
from repro.launch.serve import VOCAB, main

POOL = ("h2o-danube-3-4b", "mamba2-780m", "starcoder2-7b")

if __name__ == "__main__":
    main(["--kind", "awc", "--rounds", "25", "--n", "2", "--rho", "0.6",
          "--train-first", "1", "--dispatch", "continuous",
          "--tenants", "4", "--max-len", "64"],
         configs=[dataclasses.replace(get_config(nm).reduced(), vocab=VOCAB)
                  for nm in POOL])
