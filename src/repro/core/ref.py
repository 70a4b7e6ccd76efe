"""Pure-jnp reference oracle for stencil application.

This is the ground truth against which the ISA VM, the Pallas kernels, and
the distributed halo-exchange step are all validated.

Boundary handling: every oracle honors ``spec.boundary`` — the per-sweep
semantics are "extend the grid by the boundary rule, apply the taps, keep
the interior" for each of the four modes (zero / constant(c) / periodic /
reflect; see the mode table in :mod:`repro.core.stencil`).
:func:`pad_boundary` is the shared extension primitive and
:func:`reflect_index` / :func:`periodic_index` the shared ghost→interior
index maps reused by the Pallas engine and the distributed halo fix-up.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .stencil import (StencilSpec, _classify, as_stages, factor_taps,
                      parse_boundary)


def periodic_index(idx, n: int):
    """Wrap (possibly out-of-range) coordinates into ``[0, n)`` — the
    ghost→interior map of ``boundary="periodic"`` (numpy ``mode="wrap"``).
    Works on numpy arrays, jnp arrays and traced values alike."""
    return idx % n


def reflect_index(idx, n: int):
    """Fold (possibly out-of-range) coordinates into ``[0, n)`` by mirror
    reflection about the edge *elements* — the ghost→interior map of
    ``boundary="reflect"`` (numpy ``mode="reflect"``: period ``2n-2``, edge
    not repeated; a size-1 axis degenerates to index 0).  Works on numpy
    arrays, jnp arrays and traced values alike."""
    if n == 1:
        return idx * 0
    period = 2 * n - 2
    m = idx % period
    xp = jnp if isinstance(idx, jax.Array) else np
    return xp.where(m < n, m, period - m)


def shift_along(x, s: int, axis: int):
    """``y[j] = x[(j + s) mod ext]`` along ``axis`` for a static ``s``:
    two static slices and a concatenation, which every backend lowers
    (Mosaic included, where a gather does not)."""
    ext = x.shape[axis]
    s %= ext
    if s == 0:
        return x
    return jax.lax.concatenate(
        [jax.lax.slice_in_dim(x, s, ext, axis=axis),
         jax.lax.slice_in_dim(x, 0, s, axis=axis)], axis)


def reflect_gather(x, axis: int, g0, n: int, depth: int):
    """Overwrite ghosts along ``axis`` with their mirror source.

    ``x`` spans global coordinates ``[g0, g0 + x.shape[axis])`` of an
    ``n``-point grid axis (``g0`` may be traced).  Every element up to
    ``depth`` layers outside the grid is replaced by the element at the
    fold of its own coordinate; deeper positions and in-grid elements
    are left alone.  The ghost ``k`` layers out sits a static distance
    from its source, so each depth is one static shift plus a select on
    the global coordinate — no gather.  True ghost mirrors always land
    inside the array.  Shared by the fused-sweep ghost restoration and
    the distributed edge fix-up.
    """
    g = g0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    for k in range(1, depth + 1):
        for ghost in (-k, n - 1 + k):
            src = int(reflect_index(np.asarray(ghost), n))
            x = jnp.where(g == ghost, shift_along(x, src - ghost, axis), x)
    return x


def _pad_with(pad_fn, grid, widths, mode, value):
    pad = [(int(w), int(w)) for w in widths]
    if mode == "zero":
        return pad_fn(grid, pad)
    if mode == "constant":
        return pad_fn(grid, pad, constant_values=value)
    if mode == "periodic":
        return pad_fn(grid, pad, mode="wrap")
    if mode == "reflect":
        return pad_fn(grid, pad, mode="reflect")
    raise ValueError(f"unknown boundary mode {mode!r}")


def pad_boundary(grid: jax.Array, widths, mode: str = "zero",
                 value: float = 0.0) -> jax.Array:
    """Extend ``grid`` by ``widths[d]`` ghost layers per side of dim ``d``
    according to the boundary ``mode``.

    The ghost values are *bitwise copies* of interior elements for
    ``periodic``/``reflect`` (arbitrarily deep: wrap repeats, reflect
    folds with period ``2n-2``), and the literal fill for
    ``zero``/``constant`` — so any implementation that builds its halo
    through this helper agrees bit-for-bit with any other.
    """
    if mode == "constant":
        value = jnp.asarray(value, grid.dtype)
    return _pad_with(jnp.pad, grid, widths, mode, value)


def tap_sum(windows, coeffs, dtype) -> jax.Array:
    """``sum_k coeffs[k] * windows[k]`` with a *defined* f64 order.

    XLA's simplifier regroups floating-point add chains, and two
    independently compiled programs (this oracle under jit vs the Pallas
    engine's tile-local graphs) can pick different groupings, breaking
    bit-identity at 1 ulp — ``optimization_barrier`` does not survive
    CPU backend simplification.  For float64, the validation dtype, the
    products are materialized and summed through a ``fori_loop`` carry:
    XLA cannot reassociate across loop iterations, so every
    implementation that routes its accumulation through this helper
    agrees bit-for-bit, including the pure-numpy oracle
    (:func:`tap_sum_numpy` walks the identical order).  Narrower
    dtypes keep the plain chain (stencils are bandwidth-bound, the
    regrouping is perf-irrelevant, and f32/bf16 parity is
    tolerance-checked anyway).

    Separable (structure-specialized) specs don't flatten to one call
    of this helper: their pinned order *is the factored order* —
    :func:`factored_window_apply` routes each 1-D factor pass and the
    final term-sum through ``tap_sum``, in the term/offset order fixed
    by :func:`repro.core.stencil.factor_taps`, applied identically by
    the jnp oracle, the numpy oracle, the Pallas kernel and the
    distributed shard-local path (star/dense specs keep the plain tap
    order below).
    """
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(jnp.float64):
        prods = jnp.stack([jnp.asarray(c, dtype) * w
                           for c, w in zip(coeffs, windows)])
        return jax.lax.fori_loop(
            0, len(coeffs), lambda i, acc: acc + prods[i],
            jnp.zeros_like(prods[0]))
    acc = jnp.zeros(windows[0].shape, dtype)
    for c, w in zip(coeffs, windows):
        acc = acc + jnp.asarray(c, dtype) * w
    return acc


def tap_sum_numpy(windows, coeffs, dtype) -> np.ndarray:
    """Numpy analogue of :func:`tap_sum`: products accumulated from zero
    in tap order — arithmetic-identical to the f64 ``fori_loop`` carry,
    so the numpy and jnp oracles stay bit-equal in f64."""
    dtype = np.dtype(dtype)
    acc = np.zeros(windows[0].shape, dtype)
    for c, w in zip(coeffs, windows):
        acc = acc + dtype.type(c) * w
    return acc


#: Name scope of every tap-window slice, so the jaxpr lint can count
#: tap fetches apart from the other static slices of an executor.
TAP_WINDOW_SCOPE = "tap_window"


def _slice_jnp(x, starts, sizes):
    with jax.named_scope(TAP_WINDOW_SCOPE):
        return jax.lax.slice(x, tuple(starts),
                             tuple(s + n for s, n in zip(starts, sizes)))


def _slice_np(x, starts, sizes):
    return x[tuple(slice(s, s + n) for s, n in zip(starts, sizes))]


def factored_window_apply(x, terms, halo, out_shape, dtype, *,
                          slice_fn=_slice_jnp, tsum=tap_sum):
    """One structure-specialized stencil application of a factored tap
    set to window ``x`` (shape ``out_shape + 2*halo`` per dim).

    Each :class:`~repro.core.stencil.FactorTerm` runs as sequential 1-D
    axis passes; a pass consumes its factor's radius along its axis and
    trims every axis that carries no later factor down to the interior,
    so a single-factor (star) term slices ``x`` exactly like the dense
    path and a multi-factor (separable) term touches
    ``sum(len(f.offsets))`` windows instead of the box product.  Every
    pass and the final term-sum accumulate through ``tsum``
    (:func:`tap_sum`), so the factored order is pinned in f64 — the
    numpy variant (``slice_fn=_slice_np, tsum=tap_sum_numpy``) walks the
    identical arithmetic and stays bit-equal.
    """
    ndim = len(out_shape)
    vals = []
    for term in terms:
        radius = {f.axis: f.radius for f in term.factors}
        org = [-h for h in halo]                # window coord of y's origin
        y = x
        pending = [f.axis for f in term.factors]
        for f in term.factors:
            pending = pending[1:]               # axes with later factors
            new_org = [-(radius[d] if d in pending else 0)
                       for d in range(ndim)]
            ext = [n - 2 * o for n, o in zip(out_shape, new_org)]
            wins = []
            for off in f.offsets:
                starts = [new_org[d] - org[d] + (off if d == f.axis else 0)
                          for d in range(ndim)]
                wins.append(slice_fn(y, starts, ext))
            y = tsum(wins, f.coeffs, dtype)
            org = new_org
        vals.append(y)
    if len(vals) == 1:
        return vals[0]
    return tsum(vals, (1.0,) * len(vals), dtype)


def _window_apply(x, taps, halo, cur, acc_dtype, terms):
    """One stencil application on window ``x``: the taps slice ``halo``
    layers off per side, producing shape ``cur``.  Dispatches on the
    factored ``terms`` (separable) vs the dense per-tap path, both
    accumulating through :func:`tap_sum` — the pinned f64 order of the
    fused core (:func:`masked_window_pipeline`)."""
    if terms is not None:
        return factored_window_apply(x, terms, halo, cur, acc_dtype)
    return tap_sum(
        [_slice_jnp(x, tuple(h + o for h, o in zip(halo, off)), cur)
         for off, _ in taps],
        [c for _, c in taps], acc_dtype)


def _restore_ghosts(acc, mode, value, g0s, grid_shape, depth):
    """Restore boundary ghosts of an intermediate window ``acc`` whose
    dim-``d`` extent starts at global coordinate ``g0s[d]`` of a
    ``grid_shape`` grid and reaches at most ``depth[d]`` layers past
    either grid edge that matters — the closed form of the oracle
    re-padding before the next application:

    * ``zero`` / ``constant``: out-of-grid positions take the fill value
      (which also kills values leaking in from any alignment padding);
    * ``reflect``: out-of-grid positions re-mirror from the interior
      (:func:`reflect_gather`, whose sources provably lie inside the
      window);
    * ``periodic``: nothing — periodic ghosts evolve correctly on their
      own (they stay bitwise equal to their wrapped interior sources).
    """
    ndim = acc.ndim
    if mode in ("zero", "constant"):
        valid = None
        for d in range(ndim):
            coords = g0s[d] + jax.lax.broadcasted_iota(jnp.int32,
                                                       acc.shape, d)
            vd = (coords >= 0) & (coords < grid_shape[d])
            valid = vd if valid is None else valid & vd
        fill = jnp.asarray(value if mode == "constant" else 0.0, acc.dtype)
        return jnp.where(valid, acc, fill)
    if mode == "reflect":
        for d in range(ndim):
            acc = reflect_gather(acc, d, g0s[d], grid_shape[d], depth[d])
        return acc
    if mode != "periodic":
        raise ValueError(f"unknown boundary mode {mode!r}")
    return acc


def masked_window_pipeline(window: jax.Array, stages, out_shape,
                           sweeps: int, starts, grid_shape,
                           acc_dtype) -> jax.Array:
    """Apply ``sweeps`` fused applications of a stage chain to one
    widened window — the one fused core of the Pallas kernel
    (``starts`` = ``program_id * tile``), the distributed shard-local
    path (``starts`` = the shard's global offset, a traced
    ``axis_index`` value) and the slab executor.  A spec runs as its
    one-stage chain (:func:`~repro.core.stencil.as_stages`).

    ``window`` carries ``sweeps * H`` ghost layers per side around an
    ``out_shape`` interior at global coordinate ``starts`` of a
    ``grid_shape`` grid, where ``H`` is the per-dim **sum of the stage
    halos**: each stage application consumes its own radius, so the
    final result is exactly ``out_shape``.  The caller must have filled
    the ghosts with the boundary extension of ``stages[0]`` — the first
    consumer (see :func:`pad_boundary` / the distributed halo
    exchange).  ``out_shape``/``grid_shape`` must be static.

    After each stage application (except the last overall), ghost
    elements — those whose *global* coordinate falls outside the true
    grid — are restored to the boundary extension of the **next stage
    to run**, ``stages[(k+1) % n]``, wrapping across applications
    (:func:`_restore_ghosts`): fill for ``zero``/``constant``, an
    in-window mirror for ``reflect``, nothing for ``periodic`` (a
    stencil applied to a periodically extended window keeps its ghosts
    bitwise equal to their wrapped interior counterparts).  That
    per-consumer restoration is exactly the closed form of the chained
    oracle re-padding with each stage's own mode, so f64 results are
    bit-identical to ``sweeps`` chained :func:`apply_pipeline` (for one
    stage, :func:`apply_stencil`) calls.  Tile-local restoration is
    impossible for a periodic stage inside a mixed chain (periodic
    ghosts are only correct while *every* stage keeps them periodic),
    which is why lowering refuses to fuse such pipelines — see
    :func:`repro.core.stencil.fusable`.

    Per-stage compute dispatches on each stage's own structure class
    (:func:`repro.core.stencil.factor_taps`) through
    :func:`_window_apply`: separable stages run
    :func:`factored_window_apply`, star and dense stages the per-tap
    path, each accumulating through :func:`tap_sum` in its pinned f64
    order.
    """
    ndim = len(out_shape)
    stages = tuple(stages)
    n = len(stages)
    total = sweeps * n
    rem = tuple(sweeps * sum(s.halo[d] for s in stages)
                for d in range(ndim))           # ghost depth before stage 0
    x = window.astype(acc_dtype)
    step = 0
    for _ in range(sweeps):
        for k, stage in enumerate(stages):
            halo = stage.halo
            rem = tuple(r - h for r, h in zip(rem, halo))
            cur = tuple(t + 2 * r for t, r in zip(out_shape, rem))
            terms = (None if stage.structure == "dense"
                     else _classify(ndim, stage.taps).compute_terms)
            acc = _window_apply(x, stage.taps, halo, cur, acc_dtype, terms)
            step += 1
            if step < total:
                nxt = stages[(k + 1) % n]
                g0s = tuple(starts[d] - rem[d] for d in range(ndim))
                acc = _restore_ghosts(acc, nxt.boundary_mode,
                                      nxt.boundary_value, g0s, grid_shape,
                                      rem)
            x = acc
    return x


def execute_plan(plan, grid: jax.Array) -> jax.Array:
    """Thin ``ref``-backend executor of one lowered
    :class:`~repro.core.plan.ExecutionPlan`: ``plan.sweeps`` chained
    oracle applications (ghost strategy ``"pad"`` — re-extend via
    :func:`pad_boundary` before every application, which is what
    :func:`apply_stencil` does).  All decisions were made at lowering
    time; this function only executes them."""
    if plan.backend != "ref":
        raise ValueError(f"not a ref plan: backend={plan.backend!r}")
    out = grid
    for _ in range(plan.sweeps):
        for stage in as_stages(plan.spec):
            out = apply_stencil(stage, out)
    return out


def apply_pipeline(pipeline, grid: jax.Array) -> jax.Array:
    """One full application of a stage chain: ``stages[0]`` through
    ``stages[-1]``, each as one :func:`apply_stencil` sweep under its own
    boundary mode and structure — the **ground-truth chained oracle**
    every fused pipeline executor is validated against (bit-identical in
    f64).  Accepts a :class:`~repro.core.stencil.StencilPipeline` or any
    sequence of specs."""
    for stage in (pipeline.stages if hasattr(pipeline, "stages")
                  else tuple(pipeline)):
        grid = apply_stencil(stage, grid)
    return grid


def run_pipeline(pipeline, grid: jax.Array, iters: int) -> jax.Array:
    """``iters`` chained applications of the full stage chain."""

    def body(g, _):
        return apply_pipeline(pipeline, g), None

    final, _ = jax.lax.scan(body, grid, None, length=iters)
    return final


def apply_stencil(spec: StencilSpec, grid: jax.Array) -> jax.Array:
    """``out[p] = sum_k c_k * in[p + off_k]``, one sweep; taps past the
    edge are served by ``spec.boundary`` (zero / constant / periodic /
    reflect) and compute dispatches on ``spec.structure`` (star/separable
    specs run the factored path, in the same pinned order as every other
    layer)."""
    if grid.ndim != spec.ndim:
        raise ValueError(f"grid rank {grid.ndim} != spec ndim {spec.ndim}")
    halo = spec.halo
    padded = pad_boundary(grid, halo, spec.boundary_mode,
                          spec.boundary_value)
    terms = factor_taps(spec).compute_terms
    if terms is not None:
        return factored_window_apply(padded, terms, halo, grid.shape,
                                     grid.dtype)
    windows = [
        _slice_jnp(padded, tuple(h + o for h, o in zip(halo, off)),
                   grid.shape)
        for off, _ in spec.taps
    ]
    return tap_sum(windows, spec.coeffs, grid.dtype)


def run_iterations(spec: StencilSpec, grid: jax.Array, iters: int) -> jax.Array:
    """Jacobi time-stepping: out-of-place sweep, swap, repeat."""

    def body(g, _):
        return apply_stencil(spec, g), None

    final, _ = jax.lax.scan(body, grid, None, length=iters)
    return final


def pad_boundary_numpy(grid: np.ndarray, widths, mode: str = "zero",
                       value: float = 0.0) -> np.ndarray:
    """Numpy analogue of :func:`pad_boundary` (independent of jax)."""
    return _pad_with(np.pad, grid, widths, mode, value)


def apply_stencil_numpy(spec: StencilSpec, grid: np.ndarray) -> np.ndarray:
    """Loop-free numpy oracle (independent of jax): ``O(points x
    tap_ops)`` — dispatches on ``spec.structure`` exactly like
    :func:`apply_stencil`, walking the identical factored order so the
    two stay bit-equal in f64."""
    halo = spec.halo
    padded = pad_boundary_numpy(grid, halo, spec.boundary_mode,
                                spec.boundary_value)
    terms = factor_taps(spec).compute_terms
    if terms is not None:
        return factored_window_apply(padded, terms, halo, grid.shape,
                                     grid.dtype, slice_fn=_slice_np,
                                     tsum=tap_sum_numpy)
    out = np.zeros_like(grid)
    for off, coeff in spec.taps:
        idx = tuple(
            slice(h + o, h + o + n) for h, o, n in zip(halo, off, grid.shape)
        )
        out = out + coeff * padded[idx]
    return out


def apply_stencil_loops(spec: StencilSpec, grid: np.ndarray) -> np.ndarray:
    """Scalar triple-loop oracle (the paper's Fig. 2 pseudo-code), slow.

    Only used in tests on tiny grids to anchor the vectorized oracles.
    Serves out-of-grid taps point by point from the spec's boundary mode
    table, the most literal statement of the semantics.
    """
    mode, value = parse_boundary(spec.boundary)
    out = np.zeros_like(grid)
    shape = grid.shape
    for p in np.ndindex(*shape):
        acc = 0.0
        for off, coeff in spec.taps:
            q = tuple(pi + oi for pi, oi in zip(p, off))
            inside = all(0 <= qi < ni for qi, ni in zip(q, shape))
            if inside:
                acc += coeff * grid[q]
            elif mode == "constant":
                acc += coeff * value
            elif mode == "periodic":
                acc += coeff * grid[tuple(periodic_index(qi, ni)
                                          for qi, ni in zip(q, shape))]
            elif mode == "reflect":
                acc += coeff * grid[tuple(int(reflect_index(qi, ni))
                                          for qi, ni in zip(q, shape))]
            # zero: out-of-grid taps contribute nothing
        out[p] = acc
    return out
