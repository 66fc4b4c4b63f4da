"""A stand-in reference for the harness's tests, named by a configuration
file's ``reference``: it records each call in ``benchtiny.REFERENCE_CALLS``
and answers with unit readings over ``benchtiny.REFERENCE_LEAVES``."""
from __future__ import annotations

import benchtiny as bt


def accept(m: dict) -> None:
    bt.REFERENCE_CALLS.append(("accept", dict(m)))


def run_training(m, job, seed, batches, keep, weight, quant=None) -> dict:
    bt.REFERENCE_CALLS.append(("run_training", dict(m), seed, len(batches),
                               None if keep is None else keep.shape, quant))
    ones = {k: 1.0 for k in bt.REFERENCE_LEAVES}
    return {"losses": [1.0] * len(batches), "grad0": ones,
            "delta": dict(ones)}
