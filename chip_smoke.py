#!/usr/bin/env python
"""Smoke run of the fused stencil engine on a TPU.

Drives the main path once through the entry points a user calls —
``CasperEngine.run`` with the Pallas backend and ``AsyncStencilServer``
— at grid sizes stencil users run, on random data made from ``--seed``,
and checks every result against the ``backend="ref"`` oracle on the same
device.  The numbers it prints (compile seconds, warm wall seconds,
max error) describe this smoke run only; they are not benchmark
results.

    python chip_smoke.py            # one chip: the default phases
    python chip_smoke.py --mesh     # four chips: heat3d on a 2x2 mesh
                                    # vs the one-chip result, nothing else

It exits non-zero, before printing any result, when JAX finds no TPU.
The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Tolerance.  All phases run in f32.  One application of a stencil with
``n`` taps and coefficient l1-norm ``L`` rounds each of its ``n``
multiply-adds once, so two correct implementations that order them
differently (Mosaic vs XLA, FMA contraction or not) differ by at most
about ``2 n eps L max|x|`` per application, and the stencil amplifies an
earlier difference by at most ``L``.  Over ``iters`` applications that
sums to ``2 iters n eps max(L, 1)^iters max|x0|``; the check allows
twice that (:func:`f32_tolerance`).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Default one-chip phases: (name, spec key, grid shape, iters, sweeps).
#: 16384^2 f32 is 1 GiB and 512^3 f32 0.5 GiB; the periodic grids sit
#: on both sides of the 4 MiB whole-grid budget the periodic path once
#: had; the rank-1 grid (64 MiB) runs the 1024-word DMA windows and the
#: reflect mirror.
ENGINE_PHASES = (
    ("jacobi2d_16384sq", "jacobi2d", (16384, 16384), 8, 4),
    ("heat3d_512cube", "heat3d", (512, 512, 512), 8, 4),
    ("jacobi2d_periodic_4096sq", "jacobi2d:periodic", (4096, 4096), 8, 4),
    ("jacobi2d_periodic_256sq", "jacobi2d:periodic", (256, 256), 8, 4),
    ("jacobi1d_reflect_16M", "jacobi1d:reflect", (1 << 24,), 8, 4),
    ("reaction_diffusion2d_4096sq", "reaction_diffusion2d", (4096, 4096),
     8, 2),
)
SERVING_REQUESTS = 16
MESH_SHAPE = (1024, 1024, 512)          # heat3d, 2 GiB f32
MESH_AXES = ("sx", "sy", None)


def _resolve_spec(key: str):
    from repro.core import PAPER_PIPELINES, PAPER_STENCILS
    name, _, boundary = key.partition(":")
    spec = PAPER_STENCILS.get(name) or PAPER_PIPELINES[name]
    return spec.with_boundary(boundary) if boundary else spec


def f32_tolerance(spec, iters: int, scale: float) -> float:
    """Allowed max |kernel - oracle| after ``iters`` f32 applications of
    ``spec`` (a spec or a pipeline) to data bounded by ``scale``; see
    the module docstring."""
    from repro.core import as_stages
    eps = float(2.0 ** -23)
    stages = as_stages(spec)
    taps = sum(s.n_taps for s in stages)
    norm = math.prod(sum(abs(c) for c in s.coeffs) for s in stages)
    return 4.0 * iters * taps * eps * max(norm, 1.0) ** iters * scale


def _random_grid(shape, seed: int, sharding=None):
    import jax
    import jax.numpy as jnp
    kwargs = {} if sharding is None else {"out_shardings": sharding}
    make = jax.jit(lambda k: jax.random.uniform(k, shape, jnp.float32),
                   **kwargs)
    return make(jax.random.key(seed))


def _require(cond, message: str) -> None:
    if not cond:
        raise RuntimeError(message)


def _compile(fn, arg, interpret: bool):
    """Lower and compile ``fn`` for ``arg``; a compiled (non-interpret)
    run must contain a Mosaic kernel."""
    t0 = time.perf_counter()
    compiled = fn.lower(arg).compile()
    compile_s = time.perf_counter() - t0
    _require(interpret or "tpu_custom_call" in compiled.as_text(),
             "compiled program holds no Mosaic kernel")
    return compiled, compile_s


def _timed(compiled, arg):
    out = compiled(arg)
    out.block_until_ready()
    t0 = time.perf_counter()
    out = compiled(arg)
    out.block_until_ready()
    return out, time.perf_counter() - t0


def _max_err(a, b) -> float:
    import jax
    import jax.numpy as jnp
    return float(jax.jit(lambda x, y: jnp.max(jnp.abs(x - y)))(a, b))


def _lowered_plans() -> list:
    from repro.core import plan as _plan
    return _plan.PLAN_CACHE.plans()


def _require_plans_compiled(interpret: bool, before=()) -> None:
    """Every pallas plan lowered since ``before`` (an earlier
    :func:`_lowered_plans`) runs in the expected mode."""
    seen = {id(p) for p in before}
    modes = {p.interpret for p in _lowered_plans()
             if p.backend == "pallas" and id(p) not in seen}
    _require(modes == {interpret}, f"pallas plans in interpret modes {modes}")


def engine_phase(name: str, spec, shape, iters: int, sweeps: int, *,
                 seed: int = 0, interpret: bool | None = None) -> dict:
    """``CasperEngine(spec, backend="pallas", sweeps, tile="auto").run``
    on a random f32 grid, checked against ``backend="ref"``."""
    import jax
    import jax.numpy as jnp
    from repro.core import CasperEngine
    eng = CasperEngine(spec, backend="pallas", sweeps=sweeps, tile="auto",
                       interpret=interpret)
    ref = CasperEngine(spec, backend="ref")
    g = _random_grid(shape, seed)
    compiled, compile_s = _compile(
        jax.jit(lambda x: eng.run(x, iters=iters)), g, eng.interpret)
    out, warm_s = _timed(compiled, g)
    want = jax.jit(lambda x: ref.run(x, iters=iters))(g)
    err = _max_err(out, want)
    tol = f32_tolerance(spec, iters, float(jnp.max(jnp.abs(g))))
    plan = eng.plan_for(shape, jnp.float32)
    return {"phase": name, "shape": list(shape), "iters": iters,
            "sweeps": sweeps, "tile": list(plan.tile),
            "strategy": plan.ghost_strategy, "compile_s": compile_s,
            "warm_s": warm_s, "max_err": err, "tol": tol,
            "ok": bool(err <= tol and jnp.all(jnp.isfinite(out)))}


def serving_phase(n_requests: int = SERVING_REQUESTS, *, seed: int = 7,
                  interpret: bool | None = None) -> dict:
    """``AsyncStencilServer(backend="pallas")`` answering the
    ``serve.loadgen`` mix; every answer is checked against the ref
    engine and a failed request fails the phase."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import CasperEngine
    from repro.core import plan as _plan
    from repro.serve import AsyncStencilServer
    from repro.serve.loadgen import mixed_requests

    interpret = _plan.resolve_interpret(interpret)
    reqs = mixed_requests(n_requests, seed=seed)
    srv = AsyncStencilServer(backend="pallas", interpret=interpret)
    t0 = time.perf_counter()
    srv.warmup(reqs)
    compile_s = time.perf_counter() - t0
    hot = reqs[0]
    run = _plan.batch_runner(srv.specs[hot.spec_name], "pallas", 1, None,
                             interpret)
    _compile(jax.jit(lambda gs: run(gs, iters=hot.iters)),
             jnp.stack([hot.grid] * 2), interpret)
    t0 = time.perf_counter()
    with srv:
        handles = [srv.submit(r) for r in reqs]
        srv.drain()
        results = [h.result() for h in handles]     # raises if one failed
    wall_s = time.perf_counter() - t0
    err, ok = 0.0, True
    for req, got in zip(reqs, results):
        spec = srv.specs[req.spec_name]
        want = np.asarray(CasperEngine(spec, backend="ref").run(
            jnp.asarray(req.grid), iters=req.iters))
        e = float(np.max(np.abs(got - want)))
        tol = f32_tolerance(spec, req.iters, float(np.max(np.abs(req.grid))))
        err = max(err, e)
        ok &= bool(e <= tol and np.all(np.isfinite(got)))
    return {"phase": "serving_loadgen_mix", "requests": len(reqs),
            "completed": sum(h.error is None for h in handles),
            "compile_s": compile_s, "wall_s": wall_s, "max_err": err,
            "ok": ok}


def mesh_phase(shape=MESH_SHAPE, iters: int = 8, *, seed: int = 0,
               interpret: bool | None = None) -> dict:
    """heat3d on a 2x2 mesh (``("sx", "sy", None)``, pallas, sweeps=4)
    against the one-chip ``CasperEngine`` result of the same grid; the
    output must be sharded over all four devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import CasperEngine, heat3d

    before = _lowered_plans()
    devices = jax.devices()[:4]
    _require(len(devices) == 4, f"the mesh phase needs 4 devices: {devices}")
    mesh = Mesh(np.array(devices).reshape(2, 2), ("sx", "sy"))
    sharding = NamedSharding(mesh, P(*MESH_AXES))
    eng = CasperEngine(heat3d(), backend="pallas", sweeps=4, tile="auto",
                       interpret=interpret)
    g = _random_grid(shape, seed, sharding)
    step = eng.distributed_fn(mesh, MESH_AXES, iters=iters)
    compiled, compile_s = _compile(step, g, eng.interpret)
    out, warm_s = _timed(compiled, g)
    shards = {s.device: s.data.shape for s in out.addressable_shards}
    want_shard = (shape[0] // 2, shape[1] // 2, shape[2])
    _require(len(shards) == 4 and set(shards.values()) == {want_shard},
             f"output not sharded over 4 devices: {shards}")
    one = jax.device_put(g, devices[0])
    one_out = jax.jit(lambda x: eng.run(x, iters=iters))(one)
    err = _max_err(out, jax.device_put(one_out, sharding))
    tol = f32_tolerance(heat3d(), iters, 1.0)
    _require_plans_compiled(eng.interpret, before)
    return {"phase": "heat3d_mesh_2x2", "shape": list(shape),
            "iters": iters, "shards": len(shards),
            "compile_s": compile_s, "warm_s": warm_s, "max_err": err,
            "tol": tol, "ok": bool(err <= tol)}


def default_phases(seed: int, interpret: bool | None = None,
                   scale: int = 1, n_requests: int = SERVING_REQUESTS):
    """Yield the one-chip phase results; ``scale`` divides every grid
    extent (a CPU rehearsal runs them tiny)."""
    before = _lowered_plans()
    for name, key, shape, iters, sweeps in ENGINE_PHASES:
        shape = tuple(max(n // scale, 1) for n in shape)
        yield engine_phase(name, _resolve_spec(key), shape, iters, sweeps,
                           seed=seed, interpret=interpret)
    yield serving_phase(n_requests, interpret=interpret)
    _require_plans_compiled(interpret if interpret is not None else False,
                            before)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the 4-chip heat3d mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (platform "
              f"{devices[0].platform!r}); refusing to run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import env as _env
    cache = _env.enable_compile_cache()
    print(f"# chip smoke run (not benchmark numbers); device "
          f"{devices[0].device_kind} x{len(devices)}; compile cache "
          f"{cache}", flush=True)
    phases = ([mesh_phase(seed=args.seed)] if args.mesh
              else default_phases(args.seed))
    ok = True
    for res in phases:
        print("smoke " + json.dumps(res), flush=True)
        ok &= res["ok"]
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
