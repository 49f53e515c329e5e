"""Multi-pod dry run: a CPU lowering tool.

Lowers and compiles one (arch, shape) cell for a production mesh built from
fake host devices (``--xla_force_host_platform_device_count``), and reports
compile time, memory and a roofline estimate.  It pins ``JAX_PLATFORMS=cpu``
before JAX is imported, so on a machine with a chip it neither builds its
mesh from the chip nor holds it.
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- the lines above MUST run before any jax-importing module -------------
import argparse  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402

if "--devices" in sys.argv:  # tests shrink the fake-device pool
    _i = sys.argv.index("--devices")
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={sys.argv[_i + 1]}")

import jax  # noqa: E402


def main() -> None:
    p = argparse.ArgumentParser(description="Multi-pod dry-run driver")
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", required=True, choices=[
        "train_4k", "prefill_32k", "decode_32k", "long_500k"])
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--optimizer", default="sketchy",
                   choices=["sketchy", "shampoo", "adam"])
    p.add_argument("--devices", type=int, default=512,
                   help="fake host device count (tests)")
    p.add_argument("--mesh", default=None,
                   help="override mesh e.g. '2x4:data,model'")
    p.add_argument("--skip-probes", action="store_true")
    p.add_argument("--smoke", action="store_true",
                   help="reduced config + tiny shape (tests)")
    p.add_argument("--skip-full", action="store_true")
    p.add_argument("--rules", default=None,
                   help="JSON logical-rule overrides (perf experiments)")
    p.add_argument("--opt-overrides", default=None,
                   help="JSON OptimizerConfig overrides")
    p.add_argument("--model-overrides", default=None,
                   help="JSON ModelConfig overrides (perf experiments)")
    p.add_argument("--microbatches", type=int, default=None,
                   help="gradient-accumulation microbatches (train cells)")
    p.add_argument("--out", default=None, help="write report JSON here")
    args = p.parse_args()

    from repro.launch.dryrun_lib import run_cell
    from repro.launch.mesh import make_mesh

    mesh = None
    if args.mesh:
        shape_s, axes_s = args.mesh.split(":")
        mesh = make_mesh(tuple(int(x) for x in shape_s.split("x")),
                         tuple(axes_s.split(",")))

    rule_overrides = json.loads(args.rules) if args.rules else None
    if rule_overrides:
        rule_overrides = {k: tuple(v) if isinstance(v, list) else v
                          for k, v in rule_overrides.items()}
    opt_overrides = json.loads(args.opt_overrides) if args.opt_overrides else None

    report = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                      optimizer=args.optimizer, mesh=mesh,
                      skip_probes=args.skip_probes, skip_full=args.skip_full,
                      rule_overrides=rule_overrides,
                      opt_overrides=opt_overrides,
                      model_overrides=(json.loads(args.model_overrides)
                                       if args.model_overrides else None),
                      microbatches=args.microbatches, smoke=args.smoke)
    text = json.dumps(report, indent=2, default=float)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()
