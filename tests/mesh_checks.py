"""Multi-device mesh checks, run in a subprocess with 8 host devices
(jax locks the device count at first init, so the main pytest process —
which must see 1 device for the smoke tests — cannot host these).

Prints 'MESH_CHECKS_OK' on success; any assertion failure is fatal.
"""
from _fake_devices import force_host_devices

force_host_devices(8)

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.field import FERMAT
from repro.core.matrices import permuted_dft_matrix
from repro.core.parity import build_parity_tables, mesh_parity_encode, reconstruct
from repro.core.shardmap_exec import (
    build_dft_tables,
    build_universal_tables,
    mesh_dft,
    mesh_universal_a2a,
)

f = FERMAT
rng = np.random.default_rng(123)
N, W = 8, 16
mesh = Mesh(np.array(jax.devices()), ("d",))
x = f.rand((N, W), rng).astype(np.uint32)


def run_sharded(body, arrs: dict):
    keys = list(arrs)

    @partial(shard_map, mesh=mesh,
             in_specs=(P("d"),) + tuple(P("d") for _ in keys),
             out_specs=P("d"))
    def step(xb, *tb):
        rows = {k: v[0] for k, v in zip(keys, tb)}
        return body(xb[0], rows)[None]

    return np.asarray(step(jnp.asarray(x), *[jnp.asarray(arrs[k]) for k in keys]))


# ---- universal A2A, full axis and groups, p in {1, 2} ----------------------
for p in (1, 2):
    C = f.rand((N, N), rng)
    t = build_universal_tables(f, [C], N, p=p)
    y = run_sharded(
        lambda v, rows: mesh_universal_a2a(v, rows["coef"], rows["corr"], t, "d"),
        {"coef": t.coef, "corr": t.corr},
    )
    assert np.array_equal(y, f.matmul(C.T, x.astype(np.int64))), f"universal p={p}"

C0, C1 = f.rand((4, 4), rng), f.rand((4, 4), rng)
tg = build_universal_tables(f, [C0, C1], N, p=1, group_stride=1)
y = run_sharded(
    lambda v, rows: mesh_universal_a2a(v, rows["coef"], rows["corr"], tg, "d"),
    {"coef": tg.coef, "corr": tg.corr},
)
exp = np.concatenate([f.matmul(C0.T, x[:4].astype(np.int64)),
                      f.matmul(C1.T, x[4:].astype(np.int64))])
assert np.array_equal(y, exp), "grouped universal"

# ---- DFT (Cor. 1 optimal path) + inverse -----------------------------------
td = build_dft_tables(f, N, 8)
y = run_sharded(lambda v, rows: mesh_dft(v, rows["ca"], rows["cb"], td, "d"),
                {"ca": td.ca.T, "cb": td.cb.T})
D = permuted_dft_matrix(f, 8, 2)
assert np.array_equal(y, f.matmul(D.T, x.astype(np.int64))), "dft fwd"
tdi = build_dft_tables(f, N, 8, inverse=True)
xi = x
x_glob = jnp.asarray(y.astype(np.uint32))
keys = ["ca", "cb"]


@partial(shard_map, mesh=mesh, in_specs=(P("d"), P("d"), P("d")), out_specs=P("d"))
def inv_step(xb, ca, cb):
    return mesh_dft(xb[0], ca[0], cb[0], tdi, "d", inverse=True)[None]


back = np.asarray(inv_step(x_glob, jnp.asarray(tdi.ca.T), jnp.asarray(tdi.cb.T)))
assert np.array_equal(back, x.astype(np.int64)), "dft inverse"

# ---- parity encode (both methods) + any-K-of-N restore ---------------------
for R in (2, 4, 8):
    for method in ("universal", "rs"):
        t = build_parity_tables(f, N, R, p=1, method=method)
        arrs = t.device_arrays()
        y = run_sharded(lambda v, rows: mesh_parity_encode(v, rows, t, "d"), arrs)
        A = t.sgrs.grs.A_direct()
        exp = f.matmul(A.T, x.astype(np.int64))
        assert np.array_equal(y[:R], exp), f"parity N={N} R={R} {method}"

t = build_parity_tables(f, N, 4, method="rs")
A = t.sgrs.grs.A_direct()
parity = f.matmul(A.T, x.astype(np.int64))
full = np.concatenate([x.astype(np.int64), parity])
for trial in range(5):
    kept = np.sort(rng.choice(N + 4, N, replace=False))
    rec = reconstruct(f, t.sgrs, kept, full[kept])
    assert np.array_equal(rec, x.astype(np.int64)), f"reconstruct {kept}"

# ---- collective-bytes sanity: specific beats universal in lowered HLO ------
def collective_bytes(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    txt = lowered.compile().as_text()
    import re

    total = 0
    for line in txt.splitlines():
        if "collective-permute" in line and "u32[" in line:
            m = re.findall(r"u32\[([\d,]*)\]", line)
            if m and "=" in line:
                dims = m[0]
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                total += 4 * n
    return total


tu = build_parity_tables(f, N, 4, p=1, method="universal")
tr = build_parity_tables(f, N, 4, p=1, method="rs")


def make_fn(t):
    arrs = t.device_arrays()
    keys = list(arrs)

    @partial(shard_map, mesh=mesh,
             in_specs=(P("d"),) + tuple(P("d") for _ in keys),
             out_specs=P("d"))
    def step(xb, *tb):
        rows = {k: v[0] for k, v in zip(keys, tb)}
        return mesh_parity_encode(xb[0], rows, t, "d")[None]

    def fn(xg):
        return step(xg, *[jnp.asarray(arrs[k]) for k in keys])

    return fn


bu = collective_bytes(make_fn(tu), jnp.asarray(x))
br = collective_bytes(make_fn(tr), jnp.asarray(x))
print(f"collective bytes universal={bu} rs={br}")
assert bu > 0 and br > 0

print("MESH_CHECKS_OK")
