"""Cluster-scale data-mapping ablation (the paper's Fig. 14 lesson on the
TPU mesh): block-contiguous 2-D shards vs innermost-dim "sliver" shards of
the same grid on 256 devices — plus the *measured* deep-halo temporal
blocking comparison (fused ``sweeps=4`` vs unfused), both read off the
compiled HLO with the trip-count-aware walker.

Casper §4.2 chooses block shapes so neighboring points share a slice and
remote traffic only crosses block boundaries; at cluster scale the analogue
is the halo surface-to-volume ratio of the shard.  A (512, 512) block has
4x512-element halos; an (8192, 32) sliver has 2x8192-element halos — the
measured collective-permute wire bytes quantify it from the compiled HLO.

For small-halo stencils the cluster bandwidth argument inverts: halo
*launches*, not wire bytes, dominate.  ``distributed_stencil_fn(...,
sweeps=t)`` exchanges one ``t*halo``-deep halo per ``t`` sweeps, so the
compiled program must show ~t× fewer collective-permute launches at
roughly equal wire volume — asserted here, not modeled.

Runs in a subprocess (needs 256 forced host devices).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

FUSED_SWEEPS = 4
FUSED_ITERS = 4

_CODE = textwrap.dedent(f"""
    from repro.configs import env as _env
    _env.set_cpu_cores(256)
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import PAPER_STENCILS, distributed_stencil_fn
    from repro.roofline import hlo_walk

    SWEEPS = {FUSED_SWEEPS}
    ITERS = {FUSED_ITERS}
    out = {{}}
    for name in ("jacobi2d", "blur2d"):
        spec = PAPER_STENCILS[name]
        shape = (8192, 8192)
        for layout, mesh_shape, axes in (
                ("blocked", (16, 16), ("sx", "sy")),
                ("sliver", (1, 256), ("sx", "sy"))):
            mesh = jax.make_mesh(mesh_shape, ("sx", "sy"))
            fn = distributed_stencil_fn(spec, mesh, list(axes), iters=2)
            x = jax.ShapeDtypeStruct(
                shape, jnp.float32,
                sharding=NamedSharding(mesh, P(*axes)))
            compiled = fn.lower(x).compile()
            t = hlo_walk.walk(compiled.as_text(), 256)
            out[f"{{name}}/{{layout}}"] = {{
                "halo_wire_bytes_per_device": t.collective_wire_bytes,
                "bytes_per_device": t.bytes,
            }}
        # deep-halo temporal blocking, blocked mesh: same iteration count,
        # fused exchanges one SWEEPS*halo-deep halo per SWEEPS sweeps.
        mesh = jax.make_mesh((16, 16), ("sx", "sy"))
        x = jax.ShapeDtypeStruct(
            shape, jnp.float32, sharding=NamedSharding(mesh, P("sx", "sy")))
        for mode, sw in (("unfused", 1), ("fused", SWEEPS)):
            fn = distributed_stencil_fn(spec, mesh, ["sx", "sy"],
                                        iters=ITERS, sweeps=sw)
            t = hlo_walk.walk(fn.lower(x).compile().as_text(), 256)
            out[f"{{name}}/{{mode}}"] = {{
                "collective_permute_launches":
                    t.coll_count.get("collective-permute", 0.0),
                "halo_wire_bytes_per_device": t.collective_wire_bytes,
            }}
    print("RESULT" + json.dumps(out))
""")


def stencil_cluster_mapping():
    # The child is a CPU-only compile study on 256 virtual host devices:
    # it pins the CPU itself and never contends for an accelerator.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", _CODE],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"cluster-mapping child failed:\n{proc.stderr}")
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT"))
    data = json.loads(line[len("RESULT"):])
    rows, detail = [], {}
    for name in ("jacobi2d", "blur2d"):
        blk = data[f"{name}/blocked"]["halo_wire_bytes_per_device"]
        slv = data[f"{name}/sliver"]["halo_wire_bytes_per_device"]
        ratio = slv / max(blk, 1.0)
        rows.append((f"stencil_cluster_halo_{name}_blocked", 0.0, blk))
        rows.append((f"stencil_cluster_halo_{name}_sliver", 0.0, slv))

        # measured fused-vs-unfused temporal blocking (compiled HLO)
        unf = data[f"{name}/unfused"]
        fus = data[f"{name}/fused"]
        launch_reduction = (unf["collective_permute_launches"]
                            / max(fus["collective_permute_launches"], 1.0))
        wire_ratio = (fus["halo_wire_bytes_per_device"]
                      / max(unf["halo_wire_bytes_per_device"], 1.0))
        # the whole point of the deep halo: ~SWEEPS x fewer launches per
        # sweep at roughly equal wire volume.
        assert launch_reduction >= 0.75 * FUSED_SWEEPS, (
            name, unf, fus)
        assert wire_ratio < 2.0, (name, unf, fus)
        rows.append((f"stencil_cluster_fused_halo_{name}_t{FUSED_SWEEPS}"
                     "_launch_reduction", 0.0, round(launch_reduction, 3)))
        rows.append((f"stencil_cluster_fused_halo_{name}_t{FUSED_SWEEPS}"
                     "_wire_ratio", 0.0, round(wire_ratio, 3)))
        detail[name] = {
            "blocked_halo_bytes": blk, "sliver_halo_bytes": slv,
            "sliver_over_blocked": ratio,
            "temporal_blocking_measured": {
                "sweeps": FUSED_SWEEPS, "iters": FUSED_ITERS,
                "unfused": unf, "fused": fus,
                "launch_reduction": launch_reduction,
                "wire_ratio_fused_over_unfused": wire_ratio,
            }}
    slivers = [d["sliver_over_blocked"] for d in detail.values()
               if isinstance(d, dict) and "sliver_over_blocked" in d]
    launches = [d["temporal_blocking_measured"]["launch_reduction"]
                for d in detail.values()
                if isinstance(d, dict) and "temporal_blocking_measured" in d]
    detail["summary"] = {
        "mean_sliver_penalty": sum(slivers) / len(slivers),
        "mean_launch_reduction": sum(launches) / len(launches),
        "paper_analogue": "Fig. 14: blocked mapping cuts remote accesses; "
                          "deep halos cut collective launches ~sweeps x",
    }
    return rows, detail
