"""The chip benchmark's harness: cell lookup, device peaks, trace reduction,
work functions and the copied traffic generator.

Everything a cell needs is found by name: `BENCHMARK.json` names the cell's
configuration and traffic mix, `chipbench/configs/<config>.json` names its
driver (`chipbench/drivers/<driver>.py`) and sits beside its plain reference
(`chipbench/configs/<config>.py`), `chipbench/traffic/<traffic>.json` holds
the mix's parameters, and each per-layer metric is read by
`chipbench/metrics/<metric>.py`.
"""
