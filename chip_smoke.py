"""Smoke run of the coded store on a TPU, through its user entry points.

    python chip_smoke.py              # one chip: the coded-storage main path
    python chip_smoke.py --chips 4    # four chips: the mesh encode/decode only

One chip runs five phases through `CodedSystem` / `CodedService` on the
`local` backend, each checked bitwise against the host numpy field oracle:

    encode_16_4    rs 16+4 on the NTT path, W = 2^21 symbols per source
                   (64 MiB of data): `codeword` and `encode_stream`
    degraded_16_4  one data and one parity position failed: `read`, then
                   `rebuild`, on the decode kernel path
    dense_6_3      HDFS's RS-6-3-1024k shape (1 MiB cells) on the dense
                   path: encode plus a one-loss degraded read
    pallas_matmul  universal 256+128 at W = 2^16: every dimension is >= 128,
                   so the compiled Pallas field matmul runs, and its
                   program must hold a `tpu_custom_call`
    service        two tenants of one `CodedService` submit 16+4 encodes;
                   one of them then runs a degraded read

`--chips 4` runs only the mesh backend (one source per chip): rs 4+4,
rs 4+2 and dft 4 encodes at W = 2^22 per chip, and an rs 4+4 decode with
two erasures, each compared with the `local` backend and the oracle.

Each phase prints one line with its shapes and the wall seconds of one
call after warm-up.  Those are smoke timings, not benchmark numbers.  The
last line is one JSON object naming the device; any failure exits nonzero
before it.  Without a TPU the script exits nonzero at once.  Everything
runs in this one process, which holds the chip(s).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: {what} is not bitwise-equal")


def _timed(fn):
    """(result, wall seconds) of fn() after one warm-up call."""
    fn()
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _report(phase: str, shapes: str, seconds: float) -> None:
    print(f"phase={phase} shapes={shapes} smoke_wall_s={seconds!r} "
          "(smoke timing, not a benchmark) bitwise=ok", flush=True)


def _oracle(plan, x: np.ndarray) -> np.ndarray:
    """x^T A on the host: A by explicit inversion (eq. 23) for the
    structured kinds, the plan's explicit block otherwise."""
    from repro.core.field import FERMAT

    A = plan.sgrs.grs.A_direct() if plan.sgrs is not None else plan.A
    return FERMAT.matmul(A.T, x)


def encode_16_4(rng) -> None:
    from repro.api import CodedSystem, CodeSpec
    from repro.core.field import FERMAT

    K, R, W = 16, 4, 1 << 21
    system = CodedSystem(CodeSpec(kind="rs", K=K, R=R), backend="local")
    _check(system.encode_plan.local_impl == "ntt", "encode_16_4 path (ntt)")
    x = FERMAT.rand((K, W), rng)
    want = _oracle(system.encode_plan, x)
    cw, dt = _timed(lambda: system.codeword(x))
    _check(np.array_equal(cw[:K], x) and np.array_equal(cw[K:], want),
           "encode_16_4 codeword")
    streamed = np.concatenate(list(system.encode_stream(x)), axis=1)
    _check(np.array_equal(streamed, want), "encode_16_4 encode_stream")
    _report("encode_16_4", f"K{K}xR{R}xW{W}", dt)


def degraded_16_4(rng) -> None:
    from repro.api import CodedSystem, CodeSpec
    from repro.core.field import FERMAT

    K, R, W = 16, 4, 1 << 21
    spec = CodeSpec(kind="rs", K=K, R=R)
    system = CodedSystem(spec, backend="local")
    x = FERMAT.rand((K, W), rng)
    cw = np.concatenate([x, _oracle(system.encode_plan, x)], axis=0)
    lost = [3, K + 1]                        # one data, one parity position
    damaged = cw.copy()
    damaged[lost] = 0                        # a read must not use these rows
    system.fail(lost)
    got, dt_read = _timed(lambda: system.read(damaged))
    _check(np.array_equal(got, x), "degraded_16_4 read")
    healed, dt_rebuild = _timed(lambda: system.fail(lost).rebuild(damaged))
    _check(np.array_equal(healed, cw) and system.failed == (),
           "degraded_16_4 rebuild")
    _report("degraded_16_4", f"K{K}xR{R}xW{W}xE{len(lost)}",
            dt_read + dt_rebuild)


def dense_6_3(rng) -> None:
    from repro.api import CodedSystem, CodeSpec
    from repro.core.field import FERMAT

    K, R, W = 6, 3, 1 << 19
    system = CodedSystem(CodeSpec(kind="rs", K=K, R=R), backend="local")
    _check(system.encode_plan.local_impl == "dense", "dense_6_3 path (dense)")
    x = FERMAT.rand((K, W), rng)
    want = _oracle(system.encode_plan, x)
    cw, dt_enc = _timed(lambda: system.codeword(x))
    _check(np.array_equal(cw[K:], want), "dense_6_3 encode")
    damaged = cw.copy()
    damaged[2] = 0
    system.fail(2)
    got, dt_read = _timed(lambda: system.read(damaged))
    _check(np.array_equal(got, x), "dense_6_3 degraded read")
    _report("dense_6_3", f"K{K}xR{R}xW{W}xE1", dt_enc + dt_read)


def pallas_matmul(rng) -> None:
    import jax
    import jax.numpy as jnp

    from repro.api import CodedSystem, CodeSpec
    from repro.api.backends import local_encode_callable
    from repro.core.field import FERMAT

    K, R, W = 256, 128, 1 << 16
    system = CodedSystem(CodeSpec(kind="universal", K=K, R=R, seed=0),
                         backend="local")
    plan = system.encode_plan
    _check(plan.local_impl == "dense", "pallas_matmul path (dense)")
    x = FERMAT.rand((K, W), rng)
    fn = local_encode_callable(plan)
    x32 = jnp.asarray(x, jnp.uint32)
    if "tpu_custom_call" not in fn.lower(x32).as_text():
        raise SystemExit("chip_smoke: pallas_matmul program holds no "
                         "tpu_custom_call (interpreted or replaced kernel)")
    _, dt = _timed(lambda: jax.block_until_ready(fn(x32)))
    _check(np.array_equal(system.encode(x), _oracle(plan, x)),
           "pallas_matmul encode")
    _report("pallas_matmul", f"K{K}xR{R}xW{W}", dt)


def service(rng) -> None:
    from repro.api import CodeSpec
    from repro.core.field import FERMAT
    from repro.launch.service import CodedService

    K, R, W, per_tenant = 16, 4, 1 << 15, 4
    spec = CodeSpec(kind="rs", K=K, R=R)
    tenants = ("acme", "zeta")
    with CodedService(backend="local") as svc:
        ref = svc.session(tenants[0], spec).encode_plan
        xs = [FERMAT.rand((K, W), rng) for _ in range(per_tenant)]
        wants = [_oracle(ref, x) for x in xs]

        def run():
            futs = [svc.submit(t, spec, "encode", x)
                    for x in xs for t in tenants]
            return [f.result(timeout=600) for f in futs]

        got, dt = _timed(run)
        _check(all(np.array_equal(g, w) for g, w in
                   zip(got, [w for w in wants for _ in tenants])),
               "service encode")
        cw = np.concatenate([xs[0], wants[0]], axis=0)
        svc.session(tenants[1], spec).fail(range(R))
        got = svc.submit(tenants[1], spec, "decode", cw).result(timeout=600)
        _check(np.array_equal(got, cw[:R]), "service degraded read")
    _report("service", f"2 tenants x {per_tenant} encodes K{K}xR{R}xW{W} "
            f"+ 1 degraded read E{R}", dt)


def mesh_4(rng) -> None:
    from repro.api import CodedSystem, CodeSpec
    from repro.core.field import FERMAT

    W = 1 << 22
    for kind, K, R in (("rs", 4, 4), ("rs", 4, 2), ("dft", 4, 4)):
        spec = CodeSpec(kind=kind, K=K, R=R)
        mesh = CodedSystem(spec, backend="mesh")
        local = CodedSystem(spec, backend="local")
        x = FERMAT.rand((K, W), rng)
        got, dt = _timed(lambda: mesh.encode(x))
        _check(np.array_equal(got, _oracle(mesh.encode_plan, x)),
               f"mesh_encode {kind} {K}+{R} vs oracle")
        _check(np.array_equal(got, local.encode(x)),
               f"mesh_encode {kind} {K}+{R} vs local")
        _report(f"mesh_encode_{kind}_{K}_{R}", f"K{K}xR{R}xW{W} per device",
                dt)
    spec = CodeSpec(kind="rs", K=4, R=4)
    mesh = CodedSystem(spec, backend="mesh")
    local = CodedSystem(spec, backend="local")
    x = FERMAT.rand((4, W), rng)
    cw = np.concatenate([x, _oracle(mesh.encode_plan, x)], axis=0)
    lost = [1, 5]
    mesh.fail(lost)
    local.fail(lost)
    got, dt = _timed(lambda: mesh.decode(cw))
    _check(np.array_equal(got, cw[lost]), "mesh_decode rs 4+4 vs oracle")
    _check(np.array_equal(got, local.decode(cw)), "mesh_decode rs 4+4 vs local")
    _report("mesh_decode_rs_4_4", f"K4xR4xW{W}xE{len(lost)} per device", dt)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase, one source per chip")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every payload")
    args = ap.parse_args()

    enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    phases = ([mesh_4] if args.chips == 4 else
              [encode_16_4, degraded_16_4, dense_6_3, pallas_matmul, service])
    rng = np.random.default_rng(args.seed)
    for phase in phases:
        phase(rng)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
