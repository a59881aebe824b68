"""The chip's peaks, the roofline, and the rule every architecture
module's least-work counts follow.

Each architecture module (``archs/<arch>.py``) gives ``decode_step(spec,
ctx)`` and ``prefill(spec, n)``: the (FLOPs, bytes) of one served step.
The rule for them:

* A count is a lower bound on the work the step must do: the operations
  its useful tokens cannot skip and the bytes it cannot help moving
  (each weight it multiplies through read once, the live cache read
  once, the new cache rows written once).
* It is computed only from what the harness records for each step: the
  live rows' context lengths of a decode step (``Record.decode_ctx``)
  and the unpadded prompt length of a prefill (``Record.prefill_n``).
  Rows padded into a batch bucket and positions padded into a prefill
  bucket add time in the trace and nothing here, so they show as a
  lower share.
* So no share of a roofline or a peak can read over 100%: where one
  does, the count is too high or the time leaves out part of the work.
  Where a step's work depends on what the harness does not record, the
  count takes the least it can be.  An expert layer, for example,
  counts the weights of at most min(experts held, routed token-expert
  pairs) experts per layer, since the routing is not recorded.

A roofline time is the larger of operations over peak FLOP/s and bytes
over peak HBM bytes/s.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The chip's peaks by ``device_kind``; a chip not in the table is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flop_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
