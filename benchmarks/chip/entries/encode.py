"""Encode: `CodedSystem.encode(x)` of (K, W) host int64 data, the R parity
rows back on the host."""
from __future__ import annotations

import numpy as np


def build(system, traffic):
    """The call one op makes, planned in set-up."""
    return system.encode


def payload(x, reference, traffic):
    return x


def expected(x, reference, traffic):
    return reference.encode(x)


def control(reference, traffic):
    """The reference in the program's place, with 16-bit symbols."""
    return lambda x: reference.encode(x.astype(np.uint16)).astype(np.uint16)
