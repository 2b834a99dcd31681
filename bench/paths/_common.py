"""What the path drivers share: a program result as a dict of numpy
arrays."""
from __future__ import annotations

import dataclasses

import numpy as np


def result_arrays(res) -> dict:
    """A program ``SimResult`` as ``{field: numpy array}`` (telemetry
    aside)."""
    return {f.name: np.asarray(getattr(res, f.name))
            for f in dataclasses.fields(res) if f.name != "telemetry"}
