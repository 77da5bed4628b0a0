"""CodedSystem backend-parity checks on 8 forced host devices (subprocess
companion of test_system.py — jax locks the device count at first init).

For every code kind, the session round-trip `encode -> fail -> read ->
heal -> encode` must produce bitwise-identical codewords, repaired
symbols, and degraded reads across all three built-in backends
("simulator", "local", "mesh"), the full `rebuild` (from the (N, W)
codeword AND from (K, W) kept survivors, streamed included) must
re-materialize the identical codeword on all three, and the mesh
backend's declared device requirement must be enforced at plan time.
A mesh encode times each of its host-edge stages once and counts the
(K, W) uint32 arrays it places and reads back.

Prints 'SYSTEM_MESH_CHECKS_OK' on success; any assertion failure is fatal.
"""
from _fake_devices import force_host_devices

force_host_devices(8)

import numpy as np

from repro.api import BackendCapabilityError, CodedSystem, CodeSpec
from repro.obs.metrics import REGISTRY

f_q = 65537
rng = np.random.default_rng(31)


def edge_delta(before, after, family, **label):
    """Calls (a histogram's count) or bytes a mesh encode added to
    `family` at `label` between two registry snapshots."""
    labels = dict(label, op="encode", backend="mesh")
    key = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))

    def at(snap):
        v = snap.get(family, {}).get("values", {}).get(key, 0)
        return v["count"] if isinstance(v, dict) else v
    return at(after) - at(before)


cases = [
    ("universal", 8, 4, (0, 9)),
    ("rs", 8, 4, (2, 4, 11)),
    ("rs", 8, 8, (0, 2, 9, 13)),
    ("lagrange", 8, 4, (1, 10)),
    ("dft", 8, 8, (5, 9, 13)),
]
for kind, K, R, erased in cases:
    spec = CodeSpec(kind=kind, K=K, R=R, W=16,
                    seed=9 if kind == "universal" else None)
    x = rng.integers(0, f_q, (K, 16))
    outs = {}
    for backend in ("simulator", "local", "mesh"):
        system = CodedSystem(spec, backend=backend)
        cw = system.codeword(x)
        system.fail(erased)
        lost = system.decode(cw)
        data = system.read(cw)
        assert np.array_equal(data, x % f_q), (kind, backend, "read")
        assert np.array_equal(lost, cw[list(sorted(erased))]), \
            (kind, backend, "decode")
        system.heal()
        before = REGISTRY.snapshot()
        assert np.array_equal(system.encode(x), cw[K:]), \
            (kind, backend, "re-encode")
        if backend == "mesh":
            after = REGISTRY.snapshot()
            for st in ("prep", "h2d", "dispatch", "d2h", "widen"):
                assert edge_delta(before, after, "edge_stage_seconds",
                                  stage=st) == 1, (kind, st)
            for d in ("h2d", "d2h"):
                assert edge_delta(before, after, "edge_bytes_total",
                                  direction=d) == K * 16 * 4, (kind, d)
        # rebuild: recompute ALL failed symbols, return the healed (N, W)
        system.fail(erased)
        assert np.array_equal(system.rebuild(cw), cw), \
            (kind, backend, "rebuild")
        assert system.failed == ()
        system.fail(erased)
        assert np.array_equal(system.rebuild(cw[list(system.kept)]), cw), \
            (kind, backend, "rebuild from survivors")
        system.fail(erased)
        streamed = np.concatenate(
            list(system.rebuild_stream(cw, chunk_w=8)), axis=1)
        assert np.array_equal(streamed, cw), \
            (kind, backend, "rebuild_stream")
        assert system.failed == ()
        outs[backend] = (cw, lost, data)
    for backend in ("local", "mesh"):
        for ya, yb in zip(outs["simulator"], outs[backend]):
            assert np.array_equal(ya, yb), (kind, backend, "parity")
    print(f"{kind} K={K} R={R} erased={erased}: 3-backend round-trip OK")

# the mesh device requirement is a plan-time capability error on this
# 8-device topology, not a deep shard_map failure
try:
    CodedSystem(CodeSpec(kind="rs", K=16, R=4), backend="mesh")
except BackendCapabilityError as exc:
    assert "devices" in str(exc)
else:
    raise AssertionError("mesh K=16 on 8 devices must fail at plan time")

print("SYSTEM_MESH_CHECKS_OK")
