import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input shape) on the 16x16 single-pod mesh AND the
2x16x16 multi-pod mesh:

    with jax.set_mesh(mesh):
        lowered  = jax.jit(step, ...).lower(*input_specs(arch, shape))
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

Results (roofline terms, collective histogram, memory) are appended to
results/dryrun/<arch>__<shape>__<mesh>.json so §Roofline and §Perf read
from them.

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape decode_32k
    python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch import input_specs as ispecs
from repro.launch.mesh import make_production_mesh
from repro.launch import roofline as rl
from repro.sharding import set_rules

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def run_one(arch: str, shape: str, multi_pod: bool,
            out_dir: str = RESULTS_DIR, verbose: bool = True,
            variant: str | None = None) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256
    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    record = {"arch": arch, "shape": shape, "mesh": mesh_name,
              "variant": variant, "status": "ok"}
    try:
        with jax.set_mesh(mesh):
            spec = ispecs.build(arch, shape, mesh, variant=variant)
            set_rules(spec.rules)
            try:
                lowered = jax.jit(spec.fn).lower(*spec.args)
                t_lower = time.perf_counter() - t0
                compiled = lowered.compile()
                t_compile = time.perf_counter() - t0 - t_lower
                mem = compiled.memory_analysis()
                if verbose:
                    print(f"[{arch} x {shape} x {mesh_name}] "
                          f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
                    print("  memory_analysis:", mem)
                cost = compiled.cost_analysis()
                if verbose:
                    c = cost[0] if isinstance(cost, list) else cost
                    print("  cost_analysis: flops=%.3e bytes=%.3e" %
                          (c.get("flops", 0), c.get("bytes accessed", 0)))
                hlo = compiled.as_text()
                roof = rl.analyze(
                    compiled, hlo, arch=arch, shape=shape,
                    mesh_name=mesh_name, chips=chips, cfg=get_config(arch),
                    ishape=INPUT_SHAPES[shape], note=spec.note)
                record.update(roofline=roof.to_dict(),
                              lower_s=t_lower, compile_s=t_compile)
                if verbose:
                    print(f"  roofline: compute {roof.compute_s:.3e}s "
                          f"memory {roof.memory_s:.3e}s "
                          f"collective {roof.collective_s:.3e}s "
                          f"-> {roof.dominant}-bound; useful flops "
                          f"{100*roof.useful_flops_ratio:.1f}%")
            finally:
                set_rules(None)
    except ispecs.Skip as e:
        record.update(status="skipped", reason=str(e))
        if verbose:
            print(f"[{arch} x {shape} x {mesh_name}] SKIPPED: {e}")
    except Exception as e:  # a failure here is a bug in the system
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc())
        if verbose:
            print(f"[{arch} x {shape} x {mesh_name}] ERROR: {e}")
    record["wall_s"] = time.perf_counter() - t0
    os.makedirs(out_dir, exist_ok=True)
    vtag = f"__{variant}" if variant else ""
    fname = f"{arch}__{shape}__{mesh_name}{vtag}.json".replace("/", "_")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(record, f, indent=1, default=str)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="comma-joined §Perf rule variants "
                         "(see sharding.specs.VARIANTS)")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                mesh_name = "2x16x16" if mp else "16x16"
                fname = os.path.join(
                    args.out, f"{arch}__{shape}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(fname):
                    with open(fname) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            continue
                rec = run_one(arch, shape, mp, args.out,
                              variant=args.variant)
                failures += rec["status"] == "error"
    print(f"\ndry-run complete; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
