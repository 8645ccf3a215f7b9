"""Static schedule of one ``sq_matmul`` kernel plan on a described TPU v5e.

Compiles the Pallas kernel for a v5e topology described on the host (no
chip needed), has the TPU compiler dump its final LLO bundles, and counts
the bundles of the kernel's inner loop per PM vreg (8 x 128 PM terms)
that one loop iteration computes, with the loop's spill stores and loads
and its vector adds and multiplies.  The counts are the compiler's static
schedule, not a timing: they rank plans, and the chip sweep
(``kernel_timing.py --sweep-sq-matmul``) says what they are worth.

    PYTHONPATH=src python benchmarks/llo_bundles.py BM BN BK KC LAYOUT [FB]

prints one JSON line.  ``BK`` of two groups (2 * KC) is enough to see the
loop body; a single-group slab has no loop, and the whole kernel's
bundles are counted instead.
"""
from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import tempfile


def _compile(bm, bn, bk, kc, layout, fb):
    """Child process: lower and compile the kernel for a described v5e."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.sq_matmul import sq_matmul_batched_pallas

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sh)

    def sq_matmul_plan(a, b, sa, sb):
        return sq_matmul_batched_pallas(a, b, sa, sb, fb=fb, bm=bm, bn=bn,
                                        bk=bk, kc=kc, pm_layout=layout,
                                        interpret=False)

    jax.jit(sq_matmul_plan).lower(sds((fb, bm, bk)), sds((fb, bk, bn)),
                                  sds((fb, bm, 1)),
                                  sds((fb, 1, bn))).compile()


def bundle_stats(bm, bn, bk, kc, layout, fb=1):
    with tempfile.TemporaryDirectory() as d:
        env = dict(os.environ, LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={d}",
                   JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
        # the dumper may abort the child once the bundles are written, so
        # its exit code is not read: the dump is
        p = subprocess.run(
            [sys.executable, __file__, "--compile", str(bm), str(bn),
             str(bk), str(kc), layout, str(fb)],
            env=env, capture_output=True, text=True, timeout=900)
        files = [f for f in glob.glob(os.path.join(d, "*-final_bundles.txt"))
                 if re.search(r"-sq_matmul_plan\.\d+-\d+-final_bundles", f)]
        if not files:
            return {"error": (p.stderr or "no bundle dump")[-600:]}
        with open(files[0]) as fh:
            lines = fh.read().splitlines()
    bundles = [ln for ln in lines if re.match(r"\s*(0x[0-9a-f]+|[0-9]+)\s", ln)]
    loop = [ln for ln in bundles if re.search(r":\s*>", ln) or " LB:" in ln]

    def count(pattern):
        return sum(len(re.findall(pattern, ln)) for ln in loop)

    pm_vregs = kc * fb * -(-bm // 8) * -(-bn // 128)
    out = {"plan": [bm, bn, bk, kc], "pm_layout": layout, "fb": fb,
           "bundles": len(bundles), "loop_bundles": len(loop),
           "spill_st": count(r"vst \[vmem:\[#allocation\d+_spill"),
           "spill_ld": count(r"vld \[vmem:\[#allocation\d+_spill"),
           "vadd": count(r"vadd\.f32"), "vmul": count(r"vmul\.f32"),
           "pm_vregs_per_iteration": pm_vregs}
    if loop:
        out["bundles_per_pm_vreg"] = round(len(loop) / pm_vregs, 3)
    else:
        out["bundles_per_pm_vreg"] = round(
            len(bundles) / (pm_vregs * (bk // kc)), 3)
    return out


if __name__ == "__main__":
    if sys.argv[1] == "--compile":
        a = sys.argv[2:]
        _compile(int(a[0]), int(a[1]), int(a[2]), int(a[3]), a[4], int(a[5]))
    else:
        a = sys.argv[1:]
        print(json.dumps(bundle_stats(int(a[0]), int(a[1]), int(a[2]),
                                      int(a[3]), a[4],
                                      int(a[5]) if len(a) > 5 else 1)))
