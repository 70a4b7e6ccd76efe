"""Continuous-batching scheduler: admission, SLOs, backpressure,
determinism, and the perfmodel bucket-close heuristic."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import perfmodel as _pm
from repro.core import ref as cref
from repro.serve import (AsyncStencilServer, RequestRejected, ServeConfig,
                         StencilRequest, StencilServer, mixed_requests,
                         poisson_workload, submit_open_loop)
from repro.serve.stencil import default_specs


def _requests(rng):
    g = lambda shape: rng.standard_normal(shape).astype(np.float32)
    return [
        StencilRequest("jacobi2d", g((12, 20)), 2),
        StencilRequest("reaction_diffusion2d", g((12, 20)), 2),
        StencilRequest("jacobi2d", g((12, 20)), 2),
        StencilRequest("jacobi1d", g((64,)), 3),
        StencilRequest("jacobi2d", g((12, 20)), 2),
        StencilRequest("reaction_diffusion2d", g((12, 20)), 2),
    ]


def test_async_results_match_oracle(rng):
    from repro.core import run_pipeline
    server = AsyncStencilServer(backend="ref", sweeps=2)
    reqs = _requests(rng)
    with server:
        handles = [server.submit(r) for r in reqs]
        server.drain()
    for req, h in zip(reqs, handles):
        spec = default_specs()[req.spec_name]
        if hasattr(spec, "stages"):
            want = run_pipeline(spec, jnp.asarray(req.grid), req.iters)
        else:
            want = cref.run_iterations(spec, jnp.asarray(req.grid),
                                       req.iters)
        np.testing.assert_allclose(h.result(), np.asarray(want), atol=1e-5)
        assert h.done() and h.error is None
        assert h.latency_s is not None and h.latency_s >= 0
    stats = server.stats()
    assert stats.n_requests == len(reqs)
    assert stats.n_rejected == stats.n_shed == 0
    assert sum(b["size"] for b in stats.buckets) == len(reqs)
    assert stats.latency_s is not None
    assert stats.latency_s["p50"] <= stats.latency_s["p99"] \
        <= stats.latency_s["max"]


@pytest.mark.filterwarnings("ignore:ServeConfig")
def test_arrival_permutation_determinism(rng):
    """Results and bucket stats are a function of the request multiset:
    submitting the same requests in three different orders yields
    bit-identical per-request results and identical sorted bucket
    identities (sizes and close reasons included)."""
    reqs = _requests(rng)
    orders = [reqs, list(reversed(reqs)), reqs[3:] + reqs[:3]]
    runs = []
    for order in orders:
        server = AsyncStencilServer(
            config=ServeConfig(max_bucket_size=2, max_wait_s=60.0),
            backend="ref", sweeps=1)
        handles = {id(r): server.submit(r) for r in order}  # pre-start
        server.stop()                                       # drain + join
        results = {k: h.result() for k, h in handles.items()}
        stats = server.stats()
        runs.append((results, stats))
    base_results, base_stats = runs[0]

    def identities(stats):
        return [(b["spec"], b["shape"], b["dtype"], b["iters"], b["size"],
                 b["close_reason"]) for b in stats.buckets]

    for results, stats in runs[1:]:
        for k in base_results:
            assert np.array_equal(base_results[k], results[k])
        assert identities(stats) == identities(base_stats)
        assert stats.close_reasons == base_stats.close_reasons
    # jacobi2d x3 with max_bucket_size=2 splits [2, 1] in every order
    assert identities(base_stats) == sorted(identities(base_stats))
    assert base_stats.close_reasons == {"full": 2, "timeout": 0,
                                        "drain": 2}


def test_deadline_miss_accounting(rng):
    server = AsyncStencilServer(backend="ref", sweeps=1)
    g = rng.standard_normal((12, 16)).astype(np.float32)
    with server:
        missed = [server.submit(StencilRequest("jacobi2d", g, 2),
                                deadline_s=0.0) for _ in range(3)]
        met = [server.submit(StencilRequest("jacobi2d", g, 2),
                             deadline_s=60.0) for _ in range(2)]
        server.drain()
    # a missed deadline still completes — it is accounted, not dropped
    for h in missed:
        assert h.deadline_missed and h.error is None
        assert h.result().shape == g.shape
    for h in met:
        assert not h.deadline_missed
    stats = server.stats()
    assert stats.n_deadline_missed == 3


def test_backpressure_sheds_at_high_water(rng):
    server = AsyncStencilServer(
        config=ServeConfig(max_bucket_size=4, queue_depth=4,
                           max_wait_s=60.0),
        backend="ref", sweeps=1)
    g = rng.standard_normal((12, 16)).astype(np.float32)
    handles = [server.submit(StencilRequest("jacobi2d", g, 2))
               for _ in range(7)]                            # pre-start
    shed = [h for h in handles if h.error is not None]
    assert len(shed) == 3                       # past the high-water mark
    assert all(h.error.error == "shed" for h in shed)
    for h in shed:
        with pytest.raises(RequestRejected):
            h.result()
    server.stop()
    for h in handles[:4]:
        assert h.error is None and h.result().shape == g.shape
    stats = server.stats()
    assert stats.n_shed == 3
    assert stats.n_requests == 7


def test_async_rejects_invalid_requests_structurally(rng):
    server = AsyncStencilServer(backend="ref", sweeps=1)
    with server:
        bad = server.submit(StencilRequest("nope",
                                           np.zeros((4, 4), np.float32), 1))
        rank = server.submit(StencilRequest("jacobi2d",
                                            np.zeros(8, np.float32), 1))
        good = server.submit(StencilRequest(
            "jacobi2d", rng.standard_normal((8, 12)).astype(np.float32), 1))
        server.drain()
    assert bad.done() and bad.error.error == "unknown-spec"
    assert rank.error.error == "rank-mismatch"
    with pytest.raises(RequestRejected, match="unknown-spec"):
        bad.result()
    assert good.error is None and good.result().shape == (8, 12)
    assert server.stats().n_rejected == 2


def test_poisson_loadgen_bit_identical_to_sequential(rng):
    """The acceptance-criterion oracle: a seeded Poisson load-gen run
    returns results bit-identical to ``serve_sequential`` on the same
    request multiset."""
    reqs = mixed_requests(24, seed=11)
    workload = poisson_workload(reqs, rate_rps=600.0, seed=5)
    server = AsyncStencilServer(config=ServeConfig.auto(600.0),
                                backend="ref", sweeps=2)
    with server:
        handles = submit_open_loop(server, workload)
        server.drain()
    seq, _ = StencilServer(backend="ref",
                           sweeps=2).serve_sequential(reqs)
    for h, want in zip(handles, seq):
        assert np.array_equal(h.result(), want)
    stats = server.stats()
    assert stats.n_requests == len(reqs)
    assert stats.n_shed == 0 and stats.n_rejected == 0


def test_x64_worker_serves_f64(rng):
    """``ServeConfig.x64`` makes the worker thread enable x64 itself
    (the jax context manager is thread-local): f64 grids stay f64 end to
    end and match the sequential oracle bit for bit."""
    from jax import enable_x64
    g = rng.standard_normal((10, 14))
    assert g.dtype == np.float64
    server = AsyncStencilServer(config=ServeConfig(x64=True),
                                backend="ref", sweeps=1)
    with server:
        h = server.submit(StencilRequest("jacobi2d", g, 3))
        server.drain()
    out = h.result()
    assert out.dtype == np.float64
    with enable_x64():
        seq, _ = StencilServer(backend="ref", sweeps=1).serve_sequential(
            [StencilRequest("jacobi2d", g, 3)])
    assert np.array_equal(out, seq[0])


@pytest.mark.filterwarnings("ignore:ServeConfig")
def test_bucket_close_reasons(rng):
    g = rng.standard_normal((12, 16)).astype(np.float32)
    server = AsyncStencilServer(
        config=ServeConfig(max_bucket_size=2, max_wait_s=0.05),
        backend="ref", sweeps=1)
    with server:
        full = [server.submit(StencilRequest("jacobi2d", g, 2))
                for _ in range(2)]              # fills a bucket -> "full"
        for h in full:
            assert h.wait(30.0)
        lone = server.submit(StencilRequest("jacobi2d", g, 5))
        assert lone.wait(30.0)                  # closes on max_wait_s
    reasons = {b["close_reason"] for b in server.stats().buckets}
    assert reasons == {"full", "timeout"}
    assert server.stats().close_reasons["full"] == 1
    assert server.stats().close_reasons["timeout"] == 1


def test_lifecycle_errors(rng):
    server = AsyncStencilServer(backend="ref", sweeps=1)
    with pytest.raises(RuntimeError, match="not started"):
        server.drain()
    server.start()
    server.stop()
    server.stop()                               # idempotent
    with pytest.raises(RuntimeError, match="stopped"):
        server.submit(StencilRequest(
            "jacobi2d", np.zeros((4, 6), np.float32), 1))
    with pytest.raises(RuntimeError, match="stopped"):
        server.start()


def test_serve_config_validation():
    from repro.analysis import check_serve_config
    for bad in (
        ServeConfig(max_bucket_size=0),
        ServeConfig(max_wait_s=-1.0),
        ServeConfig(max_bucket_size=8, queue_depth=4),
        ServeConfig(default_deadline_s=0.0),
        ServeConfig(shed_policy="drop-oldest"),
    ):
        assert any(f.severity == "error" for f in check_serve_config(bad))
        with pytest.raises(ValueError, match="invalid ServeConfig"):
            AsyncStencilServer(config=bad)
    # legal-but-suspicious: close timer eats the whole SLO budget
    sus = ServeConfig(max_wait_s=1.0, default_deadline_s=0.5)
    assert any(f.severity == "warning" for f in check_serve_config(sus))
    with pytest.warns(UserWarning, match="SLO budget"):
        AsyncStencilServer(config=sus)
    assert check_serve_config(ServeConfig()) == []


def test_bucket_close_wait_heuristic():
    """The perfmodel knob behind ``ServeConfig.auto``: wait shrinks as
    offered load grows (the bucket fills faster), never exceeds half the
    SLO budget, and never drops below one dispatch overhead."""
    lo = _pm.bucket_close_wait_s(10.0, 32)
    hi = _pm.bucket_close_wait_s(10_000.0, 32)
    assert hi <= lo
    assert _pm.bucket_close_wait_s(1e9, 32) >= _pm.SERVE_DISPATCH_OVERHEAD_S
    assert _pm.bucket_close_wait_s(10.0, 32, deadline_s=0.01) <= 0.005
    with pytest.raises(ValueError):
        _pm.bucket_close_wait_s(100.0, 0)
    auto = ServeConfig.auto(200.0, max_bucket_size=16, deadline_s=0.5)
    assert auto.max_wait_s == _pm.bucket_close_wait_s(200.0, 16,
                                                      deadline_s=0.5)
    assert auto.default_deadline_s == 0.5
