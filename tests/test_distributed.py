"""Multi-device tests: run in subprocesses with forced host devices so the
main pytest process keeps its single-device view."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(n_devices: int, body: str) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_devices} "
            + os.environ.get("XLA_FLAGS", ""))
        import jax
        import jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    return out.stdout


def test_halo_exchange_equals_global_stencil():
    """Sharded halo-exchange stencil == single-device oracle, all 6 kernels."""
    out = run_sub(8, """
        from repro.core import PAPER_STENCILS, distributed_stencil_fn
        from repro.core import ref
        from jax.sharding import NamedSharding, PartitionSpec as P

        rng = np.random.default_rng(0)
        shapes = {1: (512,), 2: (64, 48), 3: (16, 12, 10)}
        for name, spec in PAPER_STENCILS.items():
            mesh = jax.make_mesh((8,), ("sx",)) if spec.ndim == 1 else \\
                jax.make_mesh((4, 2), ("sx", "sy"))
            axes = ["sx", "sy", None][:spec.ndim]
            if spec.ndim == 1:
                axes = ["sx"]
            g = jnp.asarray(rng.standard_normal(shapes[spec.ndim]),
                            jnp.float32)
            fn = distributed_stencil_fn(spec, mesh, axes, iters=3)
            got = np.asarray(fn(g))
            want = g
            for _ in range(3):
                want = ref.apply_stencil(spec, want)
            np.testing.assert_allclose(got, np.asarray(want), atol=1e-4)
            print(name, "ok")
    """)
    assert out.count("ok") == 6


def test_distributed_temporal_blocking_matches_oracle():
    """Fused ``sweeps=t`` distributed steps == single-device oracle for
    rank 1-3 specs across 1-D, 2-D and sliver mesh layouts, including
    ``t*halo > local block`` (multi-hop gather) and remainder iters
    (``iters % sweeps != 0``), on both shard-local backends."""
    out = run_sub(8, """
        from repro.core import PAPER_STENCILS, distributed_stencil_fn
        from repro.core import ref
        from jax.sharding import NamedSharding, PartitionSpec as P

        rng = np.random.default_rng(0)
        # name, shape, mesh shape, grid_axes, sweeps, iters
        cases = [
            ("jacobi1d", (64,), (8,), ["sx"], 4, 9),          # r=1
            ("7pt1d", (32,), (8,), ["sx"], 4, 8),             # 12 > 4: 3 hops
            ("jacobi2d", (32, 48), (4, 2), ["sx", "sy"], 4, 7),
            ("blur2d", (16, 48), (1, 8), ["sx", "sy"], 4, 5), # sliver, 8 > 6
            ("heat3d", (16, 16, 8), (4, 2), ["sx", "sy", None], 4, 6),
            ("star33_3d", (8, 16, 10), (2, 4), ["sx", "sy", None], 3, 4),
        ]
        for name, shape, mshape, axes, t, iters in cases:
            spec = PAPER_STENCILS[name]
            names = ("sx", "sy")[:len(mshape)]
            mesh = jax.make_mesh(mshape, names)
            g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
            gs = jax.device_put(g, NamedSharding(mesh, P(*axes)))
            want = np.asarray(ref.run_iterations(spec, g, iters))
            for backend in ("ref", "pallas"):
                fn = distributed_stencil_fn(
                    spec, mesh, axes, iters=iters, sweeps=t, backend=backend,
                    tile="auto" if backend == "pallas" else None)
                err = np.max(np.abs(np.asarray(fn(gs)) - want))
                assert err < 1e-4, (name, backend, err)
                print(name, backend, "ok")
    """)
    assert out.count("ok") == 12


def test_distributed_fused_equals_chained_and_fewer_launches():
    """sweeps=4 is f64 bit-identical to 4 chained single-sweep distributed
    steps and to the oracle, and its compiled HLO carries ~4x fewer
    collective-permute launches."""
    run_sub(8, """
        from jax import enable_x64
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import PAPER_STENCILS, distributed_stencil_fn
        from repro.core import ref
        from repro.roofline import hlo_walk

        spec = PAPER_STENCILS["jacobi2d"]
        mesh = jax.make_mesh((4, 2), ("sx", "sy"))
        axes = ["sx", "sy"]
        with enable_x64():
            g = jnp.asarray(np.random.default_rng(1).standard_normal(
                (32, 48)), jnp.float64)
            gs = jax.device_put(g, NamedSharding(mesh, P(*axes)))
            fused = distributed_stencil_fn(spec, mesh, axes, iters=4,
                                           sweeps=4)
            chained = distributed_stencil_fn(spec, mesh, axes, iters=4,
                                             sweeps=1)
            a, b = np.asarray(fused(gs)), np.asarray(chained(gs))
            oracle = np.asarray(ref.run_iterations(spec, g, 4))
            assert (a == b).all(), np.max(np.abs(a - b))
            assert (a == oracle).all(), np.max(np.abs(a - oracle))

            x = jax.ShapeDtypeStruct(
                g.shape, g.dtype, sharding=NamedSharding(mesh, P(*axes)))
            n = {}
            for mode, fn in (("fused", fused), ("chained", chained)):
                w = hlo_walk.walk(fn.lower(x).compile().as_text(), 8)
                n[mode] = w.coll_count.get("collective-permute", 0.0)
            assert n["chained"] >= 3.0 * n["fused"], n
            print("fused bit-identical, launches", n)
    """)


def test_distributed_boundary_modes_bit_identical():
    """Boundary-condition acceptance matrix, distributed: for every mode
    (zero / constant / periodic / reflect) the fused deep-halo path is
    f64 *bit*-identical to the single-device oracle — including the
    multi-hop deep-halo case (t*halo > shard, periodic wrap-ring crossing
    several devices), a sliver mesh, and both shard-local backends."""
    out = run_sub(8, """
        from jax import enable_x64
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import PAPER_STENCILS, distributed_stencil_fn
        from repro.core import ref as cref

        rng = np.random.default_rng(0)
        # name, shape, mesh, axes, sweeps, iters, backends
        cases = [
            ("jacobi1d", (64,), (8,), ["sx"], 4, 9, ("ref", "pallas")),
            # 7pt1d halo 3, sweeps 4 -> 12-deep halo on 4-wide shards:
            # 3-hop gather; under periodic the wrap ring crosses the grid
            # edge several devices deep.
            ("7pt1d", (32,), (8,), ["sx"], 4, 8, ("ref",)),
            ("jacobi2d", (32, 48), (4, 2), ["sx", "sy"], 4, 7,
             ("ref", "pallas")),
            ("blur2d", (16, 48), (1, 8), ["sx", "sy"], 3, 5, ("ref",)),
            ("heat3d", (16, 16, 8), (4, 2), ["sx", "sy", None], 4, 6,
             ("ref", "pallas")),
        ]
        n_ok = 0
        with enable_x64():
            for name, shape, mshape, axes, t, iters, backends in cases:
                names = ("sx", "sy")[:len(mshape)]
                mesh = jax.make_mesh(mshape, names)
                g = jnp.asarray(rng.standard_normal(shape), jnp.float64)
                gs = jax.device_put(g, NamedSharding(mesh, P(*axes)))
                for boundary in ("zero", "constant(0.5)", "periodic",
                                 "reflect"):
                    spec = PAPER_STENCILS[name].with_boundary(boundary)
                    want = np.asarray(cref.run_iterations(spec, g, iters))
                    for backend in backends:
                        fn = distributed_stencil_fn(
                            spec, mesh, axes, iters=iters, sweeps=t,
                            backend=backend)
                        got = np.asarray(fn(gs))
                        assert np.array_equal(got, want), (
                            name, boundary, backend,
                            np.max(np.abs(got - want)))
                        n_ok += 1
        print("boundary matrix ok", n_ok)
    """)
    assert "boundary matrix ok 32" in out


def test_periodic_wrap_ring_has_no_extra_launches():
    """The periodic wrap-ring costs the same number of collective-permute
    launches as the zero-boundary exchange (the ring only changes the
    permutation table, not the launch count)."""
    run_sub(8, """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import PAPER_STENCILS, distributed_stencil_fn
        from repro.roofline import hlo_walk

        spec = PAPER_STENCILS["jacobi2d"]
        mesh = jax.make_mesh((4, 2), ("sx", "sy"))
        axes = ["sx", "sy"]
        x = jax.ShapeDtypeStruct((32, 48), jnp.float32,
                                 sharding=NamedSharding(mesh, P(*axes)))
        n = {}
        for boundary in ("zero", "periodic"):
            fn = distributed_stencil_fn(spec.with_boundary(boundary), mesh,
                                        axes, iters=4, sweeps=4)
            w = hlo_walk.walk(fn.lower(x).compile().as_text(), 8)
            n[boundary] = w.coll_count.get("collective-permute", 0.0)
        assert n["periodic"] == n["zero"], n
        print("ring launch parity", n)
    """)


def test_engine_distributed_fn_inherits_engine_options():
    """CasperEngine.distributed_fn picks up the engine's sweeps/backend/
    tile (they used to be silently ignored) and decomposes iters=q*t+r."""
    run_sub(8, """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import CasperEngine, jacobi2d
        from repro.core import ref

        spec = jacobi2d()
        mesh = jax.make_mesh((4, 2), ("sx", "sy"))
        g = jnp.asarray(np.random.default_rng(2).standard_normal((32, 64)),
                        jnp.float32)
        gs = jax.device_put(g, NamedSharding(mesh, P("sx", "sy")))
        eng = CasperEngine(spec, backend="pallas", sweeps=3, tile="auto")
        fn = eng.distributed_fn(mesh, ("sx", "sy"), iters=7)
        want = np.asarray(ref.run_iterations(spec, g, 7))
        err = np.max(np.abs(np.asarray(fn(gs)) - want))
        assert err < 1e-4, err
        # per-call override wins over the engine defaults
        fn1 = eng.distributed_fn(mesh, ("sx", "sy"), iters=2, sweeps=1,
                                 backend="ref")
        err1 = np.max(np.abs(np.asarray(fn1(gs))
                             - np.asarray(ref.run_iterations(spec, g, 2))))
        assert err1 < 1e-4, err1
        print("engine distributed ok", err, err1)
    """)


def test_mesh_kernel_tag_carries_shards_and_exchange_bytes():
    """The shard-local kernel's tag adds ``shards`` and the bytes a shard
    received in the block's exchange (an axis exchanged later carries
    the corners of the one before; hops past the grid's edge send
    nothing); a single-device tag keeps its seven fields; each call of
    ``distributed_fn``'s function is a ``casper.run`` span and it still
    lowers ahead of time."""
    out = run_sub(8, """
        import contextlib
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.analysis.jaxpr_lint import _walk_eqns
        from repro.core import (PAPER_PIPELINES, CasperEngine, blur2d,
                                heat3d)
        from repro.core import trace as _trace

        def tags(fn, x):
            jaxpr = jax.make_jaxpr(fn)(x).jaxpr
            return [dict(e.params["metadata"]) for e in _walk_eqns(jaxpr)
                    if e.primitive.name == "pallas_call"]

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("sx", "sy"))
        axes = ("sx", "sy", None)
        x = jax.device_put(jnp.ones((32, 32, 256), jnp.float32),
                           NamedSharding(mesh, P(*axes)))
        eng = CasperEngine(heat3d(), backend="pallas", sweeps=4, tile="auto")
        # 16x16x256 a shard, one neighbour per axis: a 4-deep face on
        # sx, then a 4-deep face 16+8 wide on sy; 6 steps add a 2-deep
        # remainder block
        four = (4 * 16 * 256 + 4 * 24 * 256) * 4
        two = (2 * 16 * 256 + 2 * 20 * 256) * 4
        got = tags(eng.distributed_fn(mesh, axes, iters=6), x)
        assert [(t["shards"], t["exchange_bytes"], t["strategy"])
                for t in got] == [("2x2", str(four), "window"),
                                  ("2x2", str(two), "window")], got

        # a 6-wide shard under an 8-deep halo: hops of 6 and 2 points,
        # 7 and 6 of the 8 shards with a sender, rows 16+16 wide
        mesh18 = jax.make_mesh((1, 8), ("sx", "sy"))
        y = jax.device_put(jnp.ones((16, 48), jnp.float32),
                           NamedSharding(mesh18, P("sx", "sy")))
        blur = CasperEngine(blur2d(), backend="pallas", sweeps=4,
                            tile="auto")
        got = tags(blur.distributed_fn(mesh18, ("sx", "sy"), iters=4), y)
        hops = 2 * (6 * 7 + 2 * 6) * 32 / 8 * 4
        assert [(t["shards"], t["exchange_bytes"]) for t in got] == [
            ("1x8", str(round(hops)))], got

        # a fused pipeline's shard-local kernel carries them too
        mesh22 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("sx", "sy"))
        z = jax.device_put(jnp.ones((32, 512), jnp.float32),
                           NamedSharding(mesh22, P("sx", "sy")))
        pipe = CasperEngine(PAPER_PIPELINES["reaction_diffusion2d"],
                            backend="pallas", sweeps=2, tile="auto")
        got = tags(pipe.distributed_fn(mesh22, ("sx", "sy"), iters=2), z)
        assert [t["shards"] for t in got] == ["2x2"], got
        assert int(got[0]["exchange_bytes"]) > 0, got

        single = tags(lambda g: eng.run(g, iters=4),
                      jnp.ones((32, 32, 256), jnp.float32))
        assert [sorted(t) for t in single] == [sorted([
            "casper", "strategy", "sweeps", "tile", "grid_steps",
            "fetch_bytes", "write_bytes"])], single

        spans = []
        @contextlib.contextmanager
        def record(name, **kw):
            spans.append(name)
            yield
        _trace.span = record
        fn = eng.distributed_fn(mesh, axes, iters=4)
        fn(x).block_until_ready()
        assert spans == [_trace.RUN], spans
        fn.lower(x).compile()              # ahead-of-time callers
        print("mesh tag ok")
    """)
    assert "mesh tag ok" in out


def test_deep_halo_exchange_validation():
    """sweeps/iters validation and the zero-iters identity."""
    run_sub(4, """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import PAPER_STENCILS, distributed_stencil_fn

        spec = PAPER_STENCILS["jacobi2d"]
        mesh = jax.make_mesh((4,), ("sx",))
        for bad in ({"sweeps": 0}, {"iters": -1}):
            try:
                distributed_stencil_fn(spec, mesh, ["sx", None], **bad)
            except ValueError:
                pass
            else:
                raise AssertionError(f"no ValueError for {bad}")
        g = jnp.asarray(np.random.default_rng(0).standard_normal((16, 8)),
                        jnp.float32)
        gs = jax.device_put(g, NamedSharding(mesh, P("sx", None)))
        fn0 = distributed_stencil_fn(spec, mesh, ["sx", None], iters=0,
                                     sweeps=4)
        assert np.array_equal(np.asarray(fn0(gs)), np.asarray(g))
        print("validation ok")
    """)


def test_sharded_train_step_matches_single_device():
    """2x2 mesh train step == unsharded train step (same loss, same grads
    semantics through the optimizer)."""
    run_sub(4, """
        from repro.configs import get_config
        from repro.models import make_arch, make_batch, ShapeCell
        from repro.models.common import init_params, abstract_params, param_shardings
        from repro.optim import AdamWConfig, init_opt_state
        from repro.sharding import ShardCtx
        from repro.train import make_train_step
        from repro.launch.mesh import make_host_mesh

        cfg = get_config("yi-9b", reduced=True)
        arch = make_arch(cfg)
        params = init_params(jax.random.PRNGKey(0), arch.param_specs(cfg))
        batch = make_batch(cfg, ShapeCell("s", 32, 4, "train"))
        opt = AdamWConfig(lr=1e-3)

        # single device
        st = init_opt_state(params, opt)
        step1 = make_train_step(arch, opt, ShardCtx(None))
        p1, s1, m1 = jax.jit(step1)(params, st, batch)

        # 2x2 mesh
        mesh = make_host_mesh(2, 2)
        ctx = ShardCtx(mesh)
        sh = param_shardings(arch.param_specs(cfg), mesh)
        params_sh = jax.tree.map(jax.device_put, params, sh)
        st2 = init_opt_state(params_sh, opt)
        step2 = make_train_step(arch, opt, ctx)
        p2, s2, m2 = jax.jit(step2)(params_sh, st2, batch)

        assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-3, \\
            (float(m1["loss"]), float(m2["loss"]))
        d = jax.tree.map(lambda a, b:
                         float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                               - b.astype(jnp.float32)))),
                         p1, p2)
        worst = max(jax.tree.leaves(d))
        assert worst < 5e-2, worst
        print("sharded == single ok", float(m1["loss"]), worst)
    """)


def test_elastic_remesh_checkpoint_restore():
    """Checkpoint on a (2,2) mesh, restore on (4,1): any-mesh restore."""
    run_sub(4, """
        import shutil
        from repro.configs import get_config
        from repro.models import make_arch
        from repro.optim import AdamWConfig
        from repro.train import Trainer, TrainLoopConfig
        from repro.launch.mesh import make_host_mesh

        shutil.rmtree("/tmp/repro_remesh_test", ignore_errors=True)
        cfg = get_config("yi-9b", reduced=True)
        arch = make_arch(cfg)
        opt = AdamWConfig(lr=1e-3)
        lc = TrainLoopConfig(total_steps=4, ckpt_every=2,
                             ckpt_dir="/tmp/repro_remesh_test", log_every=1)
        mesh1 = make_host_mesh(2, 2)
        tr = Trainer(arch, opt, lc, mesh=mesh1)
        tr.run()

        mesh2 = make_host_mesh(4, 1)
        tr2 = Trainer(arch, opt, lc, mesh=mesh2)
        assert tr2.try_resume()
        assert tr2.step == 4
        tr2.remesh(mesh2)
        m = tr2.run_step()
        assert np.isfinite(m["loss"])
        print("remesh ok", m["loss"])
    """)


def test_flash_decode_seqsharded_matches_dense():
    """The shard_map flash-decode (KV seq over 'model') must produce the
    same logits as the single-device dense decode path."""
    run_sub(4, """
        import dataclasses
        from repro.configs import get_config
        from repro.models import make_arch
        from repro.models.common import init_params, abstract_params, param_shardings
        from repro.sharding import ShardCtx
        from repro.launch.mesh import make_host_mesh

        base = get_config("yi-9b", reduced=True)
        cfg = dataclasses.replace(base, decode_kv_seq_shard=True)
        arch = make_arch(base)
        arch_fd = make_arch(cfg)
        params = init_params(jax.random.PRNGKey(0), arch.param_specs(base))
        key = jax.random.PRNGKey(5)
        b, s = 4, 16
        tokens = jax.random.randint(key, (b, s + 1), 0, base.vocab,
                                    dtype=jnp.int32)

        # reference: dense decode on the SAME mesh (isolates the flash
        # softmax-combine from generic bf16 TP partial-sum reordering)
        mesh = make_host_mesh(1, 4)
        outs = {}
        for name, c_, a_ in (("dense", base, arch),
                             ("flash", cfg, arch_fd)):
            ctx = ShardCtx(mesh)
            sh = param_shardings(a_.param_specs(c_), mesh)
            params_sh = jax.tree.map(jax.device_put, params, sh)
            st2, ln2, _ = jax.jit(lambda p, b_, a=a_, c=c_, x=ctx: a.prefill(
                p, b_, c, x, max_len=s + 16))(params_sh,
                                              {"tokens": tokens[:, :s]})
            _, _, got = jax.jit(lambda p, s_, l_, t_, a=a_, c=c_, x=ctx:
                                a.decode(p, s_, l_, t_, c, x))(
                params_sh, st2, ln2, tokens[:, s:s+1])
            outs[name] = got[:, -1]
        err = float(jnp.max(jnp.abs(outs["flash"] - outs["dense"])))
        assert err < 1e-3, err

        # and against single-device dense with a bf16-TP tolerance
        ctx0 = ShardCtx(None)
        st, ln, _ = arch.prefill(params, {"tokens": tokens[:, :s]}, base,
                                 ctx0, max_len=s + 16)
        _, _, ref = arch.decode(params, st, ln, tokens[:, s:s+1], base, ctx0)
        err0 = float(jnp.max(jnp.abs(outs["flash"] - ref[:, -1])))
        assert err0 < 0.5, err0
        print("flash decode ok", err, err0)
    """)


def test_multipod_mesh_shards_pod_axis():
    """A (2, 2, 2) pod/data/model mesh lowers + runs a sharded matmul and
    the pod axis actually partitions the batch."""
    run_sub(8, """
        from repro.launch.mesh import make_production_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        # mimic the production mesh topology at 8 devices
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        x = jnp.ones((8, 16))
        w = jnp.ones((16, 8))
        xs = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"), None)))
        ws = jax.device_put(w, NamedSharding(mesh, P(None, "model")))
        y = jax.jit(lambda a, b: a @ b)(xs, ws)
        assert y.shape == (8, 8)
        # per-device shard covers 1/4 of rows (pod*data) and 1/2 of cols
        shard = y.addressable_shards[0]
        assert shard.data.shape == (2, 4), shard.data.shape
        print("multipod ok")
    """)
