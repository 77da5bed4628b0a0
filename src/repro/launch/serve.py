"""Serving launcher: batched prefill + greedy decode on a reduced config.

Demonstrates the full request path (tokenize-stub -> prefill -> KV-cached
decode); on TPU the same decode_step lowers under the production mesh (the
decode_32k / long_500k dry-run cells).

`--coded-selfcheck` additionally runs the replica's parameters through a
`repro.api.CodedSystem` session before serving: shard, RS-parity-encode
(`system.codeword` on the local kernel backend), drop R shards
(`system.fail`), reconstruct (`system.read`), and verify bitwise — the
integrity gate a coded parameter store performs on startup.  With
`--degraded` the recovery leg runs through the session's auto-replanned
decode path instead of the host-side solve: the same cached `DecodePlan` a
degraded read would execute, exercising the repair matrix + Pallas kernel
path end to end.

`--queue-demo N` drives the batched coding queue through `system.submit`:
N concurrent encode and degraded-read decode requests are submitted from
worker threads, coalesced into streamed `run_batched` plan executions
(`launch.coding_queue.CodingQueue` underneath), and every result is
verified bitwise against a direct per-request `plan.run`.

`--service N` is the multi-tenant layer (`launch.service.CodedService`):
two tenants drive pooled sessions through one shared coding queue from
concurrent clients — their same-plan encodes coalesce ACROSS sessions —
one tenant runs a degraded read mid-run, and the per-tenant serving stats
(admission, coalescing ratio, latency percentiles) are printed.

`--chaos R,SEED` is the failure-injection scenario: first a mid-schedule
leg (a `FaultInjector` kills up to R processors at random rounds of a
running repair schedule; `repair_with_faults` restarts against each
enlarged erasure set with exact C1/C2 accounting), then a serving leg
(random `fail()`s race queued encode/decode/rebuild submissions through
one `CodedSystem`, exercising the queue's superset failover), then chaos
UNDER multi-tenant load (kills racing two tenants' queued submissions
through a `CodedService`), and finally a full `rebuild` back to health —
every result self-checked bitwise against the original codeword."""
from __future__ import annotations

import argparse
import time


def _chaos_demo(max_kills: int, seed: int, n_shards: int,
                n_parity: int) -> None:
    import numpy as np

    from ..api import CodedSystem, CodeSpec
    from ..core.field import FERMAT
    from ..core.simulator import FaultInjector, RoundNetwork
    from ..recover import repair_with_faults

    max_kills = max(1, min(int(max_kills), n_parity))
    rng = np.random.default_rng(seed)
    spec = CodeSpec(kind="rs", K=n_shards, R=n_parity)
    x = FERMAT.rand((n_shards, 128), rng)
    system = CodedSystem(spec, backend="local")
    cw = system.codeword(x)

    # -- leg 1: mid-schedule kills on the round network -------------------
    first = int(rng.integers(0, spec.N))
    net = RoundNetwork(spec.N, spec.p)
    inj = FaultInjector(net)
    # small-K repair schedules run only a handful of rounds — keep the
    # injection window inside them so kills actually land mid-schedule
    kills = inj.random_kills(rng, [i for i in range(spec.N) if i != first],
                             max_kills - 1, max_round=2)
    report = repair_with_faults(spec, cw, erased=(first,), net=net)
    assert np.array_equal(report.codeword, cw), "chaos repair mismatch"
    assert net.C1 == sum(a.C1 for a in report.attempts), "C1 accounting"
    assert net.C2 == sum(a.C2 for a in report.attempts), "C2 accounting"
    print(f"chaos mid-schedule OK: kill {{{first}}} at start + injected "
          f"{kills or 'none'}; {report.restarts} restart(s) across "
          f"{len(report.attempts)} attempt(s), final |E|="
          f"{len(report.erased)}, exact C1={net.C1} C2={net.C2} (bitwise)")

    # -- leg 2: random fail()s racing queued submissions ------------------
    futs = []
    for _ in range(6 * max_kills):
        roll = rng.random()
        if roll < 0.35 and len(system.failed) < n_parity:
            alive = [i for i in range(spec.N) if i not in system.failed]
            system.fail(int(rng.choice(alive)))
        elif roll < 0.55:
            futs.append(("encode", None, system.submit("encode", x)))
        elif roll < 0.80:
            futs.append(("decode", system.failed,
                         system.submit("decode", cw)))
        else:
            futs.append(("rebuild", None, system.submit("rebuild", cw)))
    for op, pinned, fut in futs:
        got = fut.result(timeout=120)
        ref = (cw[n_shards:] if op == "encode"
               else cw[list(pinned)] if op == "decode" else cw)
        assert np.array_equal(got, ref), f"queued {op} self-check failed"
    stats = system.stats()
    healed = system.rebuild(cw)
    assert np.array_equal(healed, cw) and system.failed == (), "rebuild"
    qs = stats.get("queue")
    system.close()
    print(f"chaos serving OK: {len(futs)} queued ops under "
          f"{len(stats['failed'])} live failures "
          f"({qs.failovers if qs else 0} superset failover(s)); "
          "rebuild -> healed, all bitwise")

    # -- leg 3: chaos UNDER multi-tenant service load ---------------------
    from .service import CodedService

    with CodedService(backend="local") as svc:
        tens = []
        for t in range(2):
            name = f"tenant{t}"
            xt = FERMAT.rand((n_shards, 64), rng)
            sess = svc.session(name, spec)
            tens.append((name, sess, xt, sess.codeword(xt)))
        sfuts = []
        for _ in range(12 * max_kills):
            name, sess, xt, cwt = tens[int(rng.integers(2))]
            roll = rng.random()
            if roll < 0.3 and len(sess.failed) < n_parity:
                alive = [i for i in range(spec.N) if i not in sess.failed]
                sess.fail(int(rng.choice(alive)))
            elif roll < 0.6:
                sfuts.append(("encode", None, cwt,
                              svc.submit(name, spec, "encode", xt)))
            elif roll < 0.85:
                sfuts.append(("decode", sess.failed, cwt,
                              svc.submit(name, spec, "decode", cwt)))
            else:
                sfuts.append(("rebuild", None, cwt,
                              svc.submit(name, spec, "rebuild", cwt)))
        for op, pinned, cwt, fut in sfuts:
            got = fut.result(timeout=120)
            ref = (cwt[n_shards:] if op == "encode"
                   else cwt[list(pinned)] if op == "decode" else cwt)
            assert np.array_equal(got, ref), f"service {op} self-check"
        sstats = svc.stats()["service"]
        print(f"chaos service OK: {len(sfuts)} ops across 2 tenants' "
              f"sessions under live kills (coalescing "
              f"{sstats['coalescing_ratio']:.2f}x, "
              f"{sstats['failovers']} failover(s)), all bitwise")


def _service_demo(n_requests: int, n_shards: int, n_parity: int) -> None:
    """Multi-tenant serving demo: two tenants drive one `CodedService`
    from concurrent clients — same spec, so their encodes coalesce across
    sessions — one tenant degraded mid-run; everything verified bitwise
    and the per-tenant serving stats printed (`service.describe()`)."""
    import threading

    import numpy as np

    from ..api import CodedSystem, CodeSpec
    from ..core.field import FERMAT
    from .service import CodedService, TenantQuota

    spec = CodeSpec(kind="rs", K=n_shards, R=n_parity)
    ref = CodedSystem(spec, backend="local")
    with CodedService(backend="local") as svc:
        svc.set_quota("acme", TenantQuota(max_inflight_ops=32, weight=2.0))
        futs: list[tuple[np.ndarray, object]] = []
        lock = threading.Lock()

        def client(tenant: str, seed: int) -> None:
            r = np.random.default_rng(seed)
            for _ in range(n_requests):
                x = FERMAT.rand((n_shards, 64), r)
                f = svc.submit(tenant, spec, "encode", x, tag=f"{tenant}/v0")
                with lock:
                    futs.append((ref.codeword(x)[n_shards:], f))

        threads = [threading.Thread(target=client, args=(t, 50 + i))
                   for i, t in enumerate(["acme", "zeta"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for want, fut in futs:
            assert np.array_equal(fut.result(timeout=120), want), \
                "service encode self-check failed"
        # one tenant degrades; its decode rides the same shared queue
        x = FERMAT.rand((n_shards, 64), np.random.default_rng(99))
        cw = ref.codeword(x)
        svc.session("zeta", spec).fail(range(n_parity))
        got = svc.submit("zeta", spec, "decode", cw).result(timeout=120)
        assert np.array_equal(got, cw[: n_parity]), "degraded read failed"
        print(svc.describe())
        print(f"service demo OK: {len(futs)} encodes from 2 tenants + 1 "
              "degraded read, all bitwise")


def _queue_demo(n_requests: int, n_shards: int, n_parity: int) -> None:
    import threading

    import numpy as np

    from ..api import CodedSystem, CodeSpec
    from ..core.field import FERMAT

    # one session handle: erasure state + both planners + the coalescing
    # queue behind system.submit (previously hand-wired plans + CodingQueue)
    system = CodedSystem(CodeSpec(kind="rs", K=n_shards, R=n_parity),
                         backend="local")
    system.fail(range(n_parity))  # worst case: first R data shards lost
    enc_plan, dec_plan = system.encode_plan, system.decode_plan

    futs: list[tuple[str, np.ndarray, object]] = []
    lock = threading.Lock()

    def client(seed: int) -> None:
        r = np.random.default_rng(seed)
        x = FERMAT.rand((n_shards, int(r.integers(64, 512))), r)
        fe = system.submit("encode", x)
        full = system.codeword(x)
        v = full[list(system.kept)]
        fd = system.submit("decode", v)
        with lock:
            futs.append(("encode", x, fe))
            futs.append(("decode", v, fd))

    threads = [threading.Thread(target=client, args=(1000 + i,))
               for i in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for op, payload, fut in futs:
        got = fut.result(timeout=120)
        ref = (enc_plan if op == "encode" else dec_plan).run(payload)
        assert np.array_equal(got, ref), f"queued {op} != direct run"
    stats = system.stats()
    system.close()
    s = stats["queue"]
    print(f"coding queue OK: {s.requests} requests in {s.batches} batched "
          f"plan executions (max coalesced {s.max_coalesced}); "
          f"encode path: {enc_plan.local_impl}")


def _coded_selfcheck(params, n_shards: int, n_parity: int,
                     degraded: bool = False) -> None:
    import numpy as np

    from ..api import CodedSystem, CodeSpec
    from ..ckpt.checkpoint import tree_to_bytes
    from ..core.field import FERMAT, bytes_to_symbols

    if n_shards % n_parity:
        raise SystemExit(
            f"--coded-parity must divide --coded-shards (Remark 4): "
            f"got {n_shards} shards, {n_parity} parity")
    raw, _ = tree_to_bytes(params)
    sym = bytes_to_symbols(raw)
    L = -(-sym.size // n_shards)
    shards = np.concatenate(
        [sym, np.zeros(n_shards * L - sym.size, np.int64)]
    ).reshape(n_shards, L)

    system = CodedSystem(CodeSpec(kind="rs", K=n_shards, R=n_parity),
                         backend="local")
    full = system.codeword(shards)  # [shards | parity]

    # worst case: the first R data shards are lost; recover from parity
    erased = tuple(range(n_parity))
    if degraded:
        system.fail(erased)
        print(system.describe())
        repaired = system.decode(full)
        assert np.array_equal(repaired, shards[: n_parity]), \
            "degraded self-check failed (repair)"
        rec = system.read(full)
        system.heal()
    else:
        from ..core.parity import reconstruct

        print(system.describe())
        kept = np.arange(n_parity, n_shards + n_parity)
        rec = reconstruct(FERMAT, system.encode_plan.sgrs, kept, full[kept])
    assert np.array_equal(rec, shards), "coded self-check failed"
    mode = "degraded DecodePlan" if degraded else "host solve"
    print(f"coded self-check OK ({mode}): {n_shards} param shards + "
          f"{n_parity} parity, recovered {n_parity} lost shards bitwise")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2_780m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--coded-selfcheck", action="store_true",
                    help="verify params survive R lost shards via RS parity")
    ap.add_argument("--degraded", action="store_true",
                    help="recover the self-check erasures via the decode "
                         "subsystem (DecodePlan) instead of the host solve")
    ap.add_argument("--coded-shards", type=int, default=8)
    ap.add_argument("--coded-parity", type=int, default=2)
    ap.add_argument("--queue-demo", type=int, default=0, metavar="N",
                    help="drive the batched coding queue with N concurrent "
                         "encode+decode clients and verify bitwise")
    ap.add_argument("--service", type=int, default=0, metavar="N",
                    help="multi-tenant CodedService demo: two tenants x N "
                         "coalescing encodes + a degraded read, verified "
                         "bitwise, per-tenant stats printed")
    ap.add_argument("--chaos", default=None, metavar="R,SEED",
                    help="failure-injection scenario: kill up to R "
                         "processors at random rounds while serving queued "
                         "encodes/decodes/rebuilds, self-check bitwise")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="capture a Chrome trace-event timeline of the whole "
                         "run (simulator rounds, stream pipeline, queue/"
                         "service ops, kernels) — load in ui.perfetto.dev")
    ap.add_argument("--metrics", action="store_true",
                    help="dump the unified metrics registry (text "
                         "exposition format) at exit")
    args = ap.parse_args()
    if args.degraded and not args.coded_selfcheck:
        ap.error("--degraded modifies the self-check; pass --coded-selfcheck")
    from ..compile_cache import enable_compile_cache

    enable_compile_cache()
    tracer = None
    if args.trace:
        from ..obs import trace as _trace

        tracer = _trace.install(_trace.Tracer())
    try:
        _run(args, ap)
    finally:
        if tracer is not None:
            from ..obs import trace as _trace

            _trace.uninstall(tracer)
            print(f"trace   : {len(tracer)} events -> "
                  f"{tracer.save(args.trace)}")
        if args.metrics:
            from ..obs.metrics import REGISTRY

            print(REGISTRY.render_text(), end="")


def _run(args, ap):
    if args.chaos:
        try:
            kills, seed = (int(t) for t in args.chaos.split(","))
        except ValueError:
            ap.error("--chaos expects R,SEED (e.g. --chaos 3,7)")
        _chaos_demo(kills, seed, args.coded_shards, args.coded_parity)
    if args.queue_demo:
        _queue_demo(args.queue_demo, args.coded_shards, args.coded_parity)
    if args.service:
        _service_demo(args.service, args.coded_shards, args.coded_parity)

    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..models import model as M

    cfg = get_config(args.arch).smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    if args.coded_selfcheck:
        _coded_selfcheck(jax.device_get(params), args.coded_shards,
                         args.coded_parity, degraded=args.degraded)
    B = args.batch
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, args.prompt_len),
                                0, cfg.vocab)
    max_len = args.prompt_len + args.gen_len + 1

    decode = jax.jit(
        lambda p, tok, pos, cache: M.decode_step(cfg, p, tok, pos, cache))
    cache = M.init_cache(cfg, B, max_len)
    tok = prompt[:, 0]
    out = [tok]
    t0 = time.time()
    for t in range(args.prompt_len + args.gen_len - 1):
        logits, cache = decode(params, tok, jnp.int32(t), cache)
        tok = (prompt[:, t + 1] if t + 1 < args.prompt_len
               else jnp.argmax(logits, -1).astype(jnp.int32))
        out.append(tok)
    toks = jnp.stack(out, 1)
    dt = (time.time() - t0) / (toks.shape[1] - 1) * 1e3
    dev = jax.devices()[0]
    print(f"arch={cfg.name} batch={B} generated {args.gen_len} tokens/seq "
          f"@ {dt:.1f} ms/token ({dev.platform} {dev.device_kind}, "
          "reduced config)")
    print("sample token ids:", toks[0, args.prompt_len:args.prompt_len + 12].tolist())


if __name__ == "__main__":
    main()
