"""Find the router's Pallas kernels in a trace and reckon their roofline.

A kernel call is one operation event of a device plane's "XLA Ops" line,
whose name is the call's HLO text:

  %topn_lp.32 = f32[4096,64,1]{...} custom-call(f32[4096,64,128]{...} %a,
      f32[4096,64,128]{...} %b, s32[4096,64,1]{...} %c), custom_call_target=...

The instruction's name carries the kernel's (`topn_lp`, `awc_fw`); its
operand shapes give the rows the call works on (every dimension but the
last: tenants x rows a tenant, as the kernel's blocks hold them) and, for
`awc_fw`, the lambdas a row (the fourth operand's last dimension). The arm
axis is padded to the 128 lanes; that padding is not work, so the arms are
the configuration's.
"""
from __future__ import annotations

import math
import re
from typing import Dict, List, Optional, Tuple

from bench import trace as trace_mod
from bench import work

NAME = re.compile(r"%([\w.-]+) = ")
SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
KERNELS = ("awc_fw", "topn_lp")


def _dims(m: str) -> Tuple[int, ...]:
    return tuple(int(x) for x in m.split(",") if x)


def _shapes(text: str) -> Tuple[List[Tuple[int, ...]], List[Tuple[int, ...]]]:
    """(result shapes, operand shapes) of one custom call's HLO text."""
    head, _, args = text.partition("custom-call(")
    args = args.split("), custom_call_target")[0]
    res = [_dims(m) for m in SHAPE.findall(head.split("=", 1)[-1])]
    return res, [_dims(m) for m in SHAPE.findall(args)]


def classify(e: trace_mod.Event) -> Optional[Tuple[str, Tuple]]:
    """('topn_lp', (rows,)) or ('awc_fw', (rows, lambdas)) or None."""
    text = str(e.stats.get("long_name") or e.name)
    m = NAME.match(text)
    if m is None or "custom-call(" not in text:
        return None
    kernel = next((k for k in KERNELS if k in m.group(1)), None)
    res, ops = _shapes(text)
    if kernel == "topn_lp" and len(ops) == 3:
        return kernel, (math.prod(ops[0][:-1]),)
    if kernel == "awc_fw" and len(ops) == 5 and len(res) == 2:
        return kernel, (math.prod(ops[0][:-1]), ops[3][-1])
    return None


def roofline(tr: trace_mod.Trace, ctx: Dict, kernel: str) -> Optional[float]:
    """Summed least time over summed device time of ``kernel``'s calls, in
    percent; None where the trace holds no call of it."""
    least = device = 0.0
    k = ctx["arms"]
    for e in trace_mod.calls_inside(tr):
        c = classify(e)
        if c is None or c[0] != kernel:
            continue
        if kernel == "topn_lp":
            ops, nbytes = work.topn_lp(c[1][0], k)
        else:
            ops, nbytes = work.awc_fw(c[1][0], k, c[1][1])
        # a call cut by the window's edge counts for its part inside
        inside = trace_mod.clipped_ns(e, tr.window)
        least += work.least_seconds(ops, nbytes, ctx["peaks"]) \
            * inside / e.dur
        device += inside / 1e9
    if device <= 0:
        return None
    return 100.0 * least / device
