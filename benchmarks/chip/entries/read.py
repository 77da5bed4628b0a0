"""Degraded read: `CodedSystem.read(v)` of the (N, W) host int64 codeword
with the traffic's `lost` rows zeroed (those positions failed for the
whole run), the K data rows back on the host.  The codewords are made by
the plain reference in set-up."""
from __future__ import annotations

import numpy as np


def build(system, traffic):
    """The call one op makes; the decode plan is made here, in set-up."""
    system.fail(traffic["lost"])
    system.decode_plan
    return system.read


def payload(x, reference, traffic):
    v = np.concatenate([x, reference.encode(x)])
    v[traffic["lost"]] = 0              # a read must not use these
    return v


def expected(x, reference, traffic):
    return x


def control(reference, traffic):
    """The reference in the program's place, with 16-bit symbols."""
    lost = traffic["lost"]
    return lambda v: reference.read(v.astype(np.uint16),
                                    lost).astype(np.uint16)
